"""Engine layer: mean host time (ms) of a wave on its worker, from
pick-up until every ticket is completed (packing, dispatches, the two
device-to-host waits): the ``copr.wave`` spans that end inside the
traced window."""
from bench.metrics import _spans

SPAN = "copr.wave"


def read(run):
    if run.trace is None or not _spans.instrumented(run.trace):
        return None
    d = _spans.durations_s(run.trace, SPAN)
    if not d:
        if run.stats["waves"]:
            raise _spans.missing(SPAN, "waves ran")
        return None
    return 1e3 * sum(d) / len(d)
