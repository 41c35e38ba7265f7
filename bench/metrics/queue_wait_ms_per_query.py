"""Serving layer: mean time (ms) a query waited in the scheduler's
queue, from ``submit`` until a wave worker took its wave: the
``copr.serve.queue`` spans that end inside the traced window."""
from bench.metrics import _spans

SPAN = "copr.serve.queue"


def read(run):
    if run.trace is None or not _spans.instrumented(run.trace):
        return None
    d = _spans.durations_s(run.trace, SPAN)
    if not d:
        if run.answered():
            raise _spans.missing(SPAN, "queries were answered")
        return None
    return 1e3 * sum(d) / len(d)
