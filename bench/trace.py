"""Profiler trace of the measured window, and its reduction to numbers.

``capture`` runs JAX's profiler over the window and flattens the
``.xplane.pb`` it writes into plain lists, ``{"planes": [{"name",
"lines": [{"name", "events": [[name, start_ns, dur_ns], ...]}]}]}``, so
that the reduction below reads a recorded trace in a test with no chip
and no profiler.

Device planes are named ``/device:<PLATFORM>:<n>``.  A device's busy
time is the union of the intervals of its ops (the ``XLA Ops`` line
where the plane has one), clipped to the window; the window itself is
the host span ``bench.window`` that the harness opens around it.
"""
from __future__ import annotations

import bisect
import glob
import os
import shutil
from contextlib import contextmanager

WINDOW_SPAN = "bench.window"
QUERY_SPAN = "bench.query"
OPS_LINE = "XLA Ops"


@contextmanager
def capture(log_dir: str, out: dict):
    """Trace the body; on exit ``out["trace"]`` holds the flat trace."""
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {log_dir}, "
                           f"found {len(paths)}")
    out["trace"] = flatten(paths[0])
    shutil.rmtree(log_dir, ignore_errors=True)


def flatten(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return {"planes": [
        {"name": p.name, "lines": [
            {"name": ln.name,
             "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in ln.events]}
            for ln in p.lines]}
        for p in pd.planes]}


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if p["name"].startswith("/device:")]


def _op_events(plane: dict) -> list:
    lines = [ln for ln in plane["lines"] if ln["name"] == OPS_LINE]
    lines = lines or plane["lines"]
    return [e for ln in lines for e in ln["events"]]


def window(trace: dict) -> tuple[float, float]:
    """(start_ns, end_ns) of the ``bench.window`` host span."""
    for p in trace["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            for name, start, dur in ln["events"]:
                if name == WINDOW_SPAN:
                    return start, start + dur
    raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")


def _union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clipped(events, lo: float, hi: float):
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def busy_intervals(trace: dict, plane: dict) -> list[tuple[float, float]]:
    lo, hi = window(trace)
    return _union((a, b) for _, a, b in _clipped(_op_events(plane), lo, hi))


def busy_s(trace: dict) -> float:
    """Seconds in which an op ran, averaged over the device planes."""
    planes = device_planes(trace)
    if not planes:
        return 0.0
    total = sum(b - a for p in planes for a, b in busy_intervals(trace, p))
    return total / len(planes) / 1e9


def window_s(trace: dict) -> float:
    lo, hi = window(trace)
    return (hi - lo) / 1e9


def op_seconds(trace: dict, match) -> float:
    """Device seconds of the ops whose name satisfies ``match`` inside
    the window, summed over every device."""
    lo, hi = window(trace)
    return sum(b - a for p in device_planes(trace)
               for name, a, b in _clipped(_op_events(p), lo, hi)
               if match(name)) / 1e9


def top_ops(trace: dict, n: int = 10) -> list[list]:
    lo, hi = window(trace)
    per: dict[str, float] = {}
    for p in device_planes(trace):
        for name, a, b in _clipped(_op_events(p), lo, hi):
            per[name] = per.get(name, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in
            sorted(per.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, n: int = 10) -> list[list]:
    """Idle device time inside the window, summed by what the host was
    doing: each gap of the first device goes to the runtime's host event
    that covers most of it (the benchmark's own ``bench.*`` spans aside),
    or to ``"no host event"``: the host was in untraced Python or idle."""
    planes = device_planes(trace)
    if not planes:
        return []
    lo, hi = window(trace)
    busy = busy_intervals(trace, planes[0])
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    starts = [a for a, _ in gaps]
    best = [(0.0, "no host event")] * len(gaps)
    for p in trace["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                if d <= 0 or name.startswith("bench."):
                    continue
                i = max(bisect.bisect_right(starts, s) - 1, 0)
                while i < len(gaps) and gaps[i][0] < s + d:
                    a, b = gaps[i]
                    cover = min(s + d, b) - max(s, a)
                    if cover > best[i][0]:
                        best[i] = (cover, name)
                    i += 1
    per: dict[str, float] = {}
    for (a, b), (_, label) in zip(gaps, best):
        per[label] = per.get(label, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in
            sorted(per.items(), key=lambda kv: -kv[1])[:n]]
