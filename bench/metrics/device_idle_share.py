"""Device: share (%) of the traced window in which no op ran on the
device (1 - union of op intervals over the window)."""
from bench import trace


def read(run):
    if run.trace is None or not trace.device_planes(run.trace):
        return None
    return 100.0 * (1.0 - trace.busy_s(run.trace) / trace.window_s(run.trace))
