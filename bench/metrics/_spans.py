"""The program's own spans in a flat trace, for the readers of the
served path's spans (``bench/trace.py`` has the trace's form).

Each reader spells the span it reads, so that a rename in the program
cannot change what the benchmark reads unseen: a reader raises when its
span is missing from a window in which the work it times was done.  A
trace with no ``copr.`` span at all comes from a program without spans
(a commit from before them): there the readers return None.
"""
import bisect

from bench import trace

PROGRAM_PREFIX = "copr."


def host_events(t: dict):
    for p in t["planes"]:
        if not p["name"].startswith("/device:"):
            for ln in p["lines"]:
                yield from ln["events"]


def instrumented(t: dict) -> bool:
    """True if the program wrote any span of its own into the trace."""
    return any(name.startswith(PROGRAM_PREFIX)
               for name, _, _ in host_events(t))


def durations_s(t: dict, name: str) -> list[float]:
    """Durations (s) of the host spans named ``name`` that end inside
    the window."""
    lo, hi = trace.window(t)
    return [d / 1e9 for n, s, d in host_events(t)
            if n == name and lo < s + d <= hi]


def durations_within_s(t: dict, name: str, parent: str) -> list[float]:
    """Durations (s) of the host spans named ``name`` that lie inside a
    span named ``parent`` on the same thread, where the parent ends
    inside the window: the child work of the parents that count."""
    lo, hi = trace.window(t)
    out = []
    for p in t["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            outer = sorted((s, s + d) for n, s, d in ln["events"]
                           if n == parent and lo < s + d <= hi)
            starts = [a for a, _ in outer]
            for n, s, d in ln["events"]:
                if n != name:
                    continue
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s + d <= outer[i][1]:
                    out.append(d / 1e9)
    return out


def answered_in_window(run) -> list:
    """The answers that came back inside the traced window (the window
    closes at ``run.t_end`` on the host clock)."""
    lo = run.t_end - trace.window_s(run.trace)
    return [r for r in run.answered() if lo <= r.done <= run.t_end]


def missing(name: str, done: str) -> RuntimeError:
    return RuntimeError(f"no {name!r} span in the traced window, though "
                        f"{done}: the program's span was renamed or lost")
