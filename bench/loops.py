"""Load generators over a ``StoreServer``: one open loop, one closed loop.

Both send each query through ``StoreServer.query_term`` or
``query_contains``, so a request's time covers its probe wave and the
exact post-filter that turns candidates into lines.  Each request leaves
a :class:`Record`; the window's metrics and the check of its answers are
made from the records alone.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import nullcontext
from dataclasses import dataclass, field

import numpy as np

from .trace import QUERY_SPAN

#: How long after the window's close an answer may still arrive.
LATE_S = 60.0


@dataclass
class Record:
    query: int                  # index into the pool
    due: float                  # monotonic time the request was due
    start: float = 0.0          # when a worker sent it
    done: float = 0.0           # when its answer came back (0: never)
    matches: np.ndarray | None = None
    candidates: int = 0
    true_batches: int = 0
    error: str = ""
    _event: threading.Event = field(default_factory=threading.Event,
                                    repr=False)


def _serve(server, query: tuple, rec: Record, span) -> None:
    _, op, text = query
    rec.start = time.monotonic()
    try:
        with span(QUERY_SPAN):
            fn = server.query_term if op == "term" else server.query_contains
            res = fn(text, timeout=LATE_S)
        rec.matches = np.asarray(res.matches, np.int64)
        rec.candidates = len(res.candidate_batches)
        rec.true_batches = int(res.true_batches)
        rec.done = time.monotonic()
    except Exception as e:          # a failed query is a record, not a crash
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        rec._event.set()


def open_loop(server, queries, due_s, order, t0: float, *, workers: int,
              span=None) -> list[Record]:
    """Send query ``order[i]`` at ``t0 + due_s[i]`` whatever the server
    does, each on a worker of its own; returns once every answer is in
    or ``LATE_S`` past the last arrival."""
    span = span or (lambda name: nullcontext())
    records = [Record(int(q), t0 + float(d)) for d, q in zip(due_s, order)]
    with ThreadPoolExecutor(max_workers=workers,
                            thread_name_prefix="bench-client") as pool:
        futures = []
        for rec in records:
            wait = rec.due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            futures.append(pool.submit(_serve, server, queries[rec.query],
                                       rec, span))
        deadline = time.monotonic() + LATE_S
        for rec in records:
            rec._event.wait(max(deadline - time.monotonic(), 0))
        for f in futures:
            if f.done():
                f.result()
    return records


def closed_loop(server, queries, sequences, t0: float, seconds: float, *,
                span=None) -> list[Record]:
    """Each client sends its sequence back to back from ``t0`` until the
    window closes; a query sent inside the window is waited for."""
    span = span or (lambda name: nullcontext())
    t_end = t0 + seconds
    per_client: list[list[Record]] = [[] for _ in sequences]

    def client(c: int) -> None:
        seq = sequences[c]
        k = 0
        wait = t0 - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        while time.monotonic() < t_end:
            rec = Record(seq[k % len(seq)], time.monotonic())
            per_client[c].append(rec)
            _serve(server, queries[rec.query], rec, span)
            k += 1

    threads = [threading.Thread(target=client, args=(c,),
                                name=f"bench-analyst-{c}", daemon=True)
               for c in range(len(sequences))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(seconds + LATE_S + 5)
    if any(th.is_alive() for th in threads):
        raise RuntimeError("a closed-loop client did not finish")
    return [r for recs in per_client for r in recs]
