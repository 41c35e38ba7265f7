"""Post-filter layer: time (ms) per answer spent decompressing and
lower-casing batches the batch LRU did not hold: the summed
``copr.postfilter.decompress`` spans inside the ``copr.postfilter``
spans that end inside the traced window, over the count of those
post-filters (the misses of an answer still in flight at the close are
left out with it).  No miss in a window reads 0."""
from bench.metrics import _spans

SPAN = "copr.postfilter"
MISS_SPAN = "copr.postfilter.decompress"


def read(run):
    if run.trace is None or not _spans.instrumented(run.trace):
        return None
    answers = _spans.durations_s(run.trace, SPAN)
    if not answers:
        if run.answered():
            raise _spans.missing(SPAN, "queries were answered")
        return None
    misses = _spans.durations_within_s(run.trace, MISS_SPAN, SPAN)
    return 1e3 * sum(misses) / len(answers)
