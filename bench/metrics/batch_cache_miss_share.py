"""Post-filter layer: share (%) of candidate batches the batch LRU did
not hold: the ``copr.postfilter.decompress`` spans (one per miss) inside
the ``copr.postfilter`` spans that end inside the traced window, over
the candidate batches of the answers that came back in it."""
from bench.metrics import _spans

SPAN = "copr.postfilter"
MISS_SPAN = "copr.postfilter.decompress"


def read(run):
    if run.trace is None or not _spans.instrumented(run.trace):
        return None
    if not _spans.durations_s(run.trace, SPAN):
        if run.answered():
            raise _spans.missing(SPAN, "queries were answered")
        return None
    cand = sum(r.candidates for r in _spans.answered_in_window(run))
    if not cand:
        return None
    misses = _spans.durations_within_s(run.trace, MISS_SPAN, SPAN)
    return 100.0 * len(misses) / cand
