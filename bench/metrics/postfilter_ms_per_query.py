"""Post-filter layer: mean time (ms) of the exact post-filter of one
answer (decompress, lower-case, substring scan, re-tokenize): the
``copr.postfilter`` spans that end inside the traced window."""
from bench.metrics import _spans

SPAN = "copr.postfilter"


def read(run):
    if run.trace is None or not _spans.instrumented(run.trace):
        return None
    d = _spans.durations_s(run.trace, SPAN)
    if not d:
        if run.answered():
            raise _spans.missing(SPAN, "queries were answered")
        return None
    return 1e3 * sum(d) / len(d)
