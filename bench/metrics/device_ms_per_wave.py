"""Engine layer: device busy time in the traced window over the device
waves the scheduler ran in it (probe, reduce and extract together)."""
from bench import trace


def read(run):
    waves = run.stats["device_waves"]
    if run.trace is None or not waves:
        return None
    return 1e3 * trace.busy_s(run.trace) / waves
