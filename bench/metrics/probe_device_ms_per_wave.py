"""Engine layer: device time (ms) per device wave of the per-segment
probe programs: the union of the device's ops that lie inside the
``copr_probe`` modules of its ``XLA Modules`` line, in the traced
window, averaged over the devices, over the device waves the scheduler
ran in it."""
import bisect

from bench import trace
from bench.metrics import _spans

MODULE = "copr_probe"
MODULES_LINE = "XLA Modules"


def read(run):
    waves = run.stats["device_waves"]
    if run.trace is None or not waves or not _spans.instrumented(run.trace):
        return None
    lo, hi = trace.window(run.trace)
    planes = trace.device_planes(run.trace)
    found, total = False, 0.0
    for p in planes:
        mods = trace._union(
            (a, b) for ln in p["lines"] if ln["name"] == MODULES_LINE
            for name, a, b in trace._clipped(ln["events"], lo, hi)
            if MODULE in name)
        found = found or bool(mods)
        starts = [a for a, _ in mods]
        pieces = []
        for _, s, e in trace._clipped(trace._op_events(p), lo, hi):
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            while i < len(mods) and mods[i][0] < e:
                a, b = max(s, mods[i][0]), min(e, mods[i][1])
                if b > a:
                    pieces.append((a, b))
                i += 1
        total += sum(b - a for a, b in trace._union(pieces))
    if not found:
        raise RuntimeError(f"no {MODULE!r} module on the device's "
                           f"{MODULES_LINE!r} line in the traced window, "
                           f"though device waves ran: the probe's jitted "
                           f"function was renamed")
    return total / len(planes) / 1e6 / waves
