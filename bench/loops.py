"""Load generators over a ``StoreServer``: one open loop, one closed loop.

Both send each query through ``send(op, text)``, which the harness binds
to the op's request on the ``StoreServer`` (``bench/ops.py``), so a
request's time covers its probe wave and the exact post-filter that
turns candidates into lines.  Each request leaves
a :class:`Record`; the window's metrics and the check of its answers are
made from the records alone.
"""
from __future__ import annotations

import queue
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass

import numpy as np

from .trace import QUERY_SPAN

#: How long after the window's close an answer may still arrive.
LATE_S = 60.0


@dataclass(slots=True)
class Record:
    """One request.  Slotted and without a lock of its own: an open loop
    makes one per arrival, and a garbage collection that the load
    generator's own objects set off would pause the server too."""
    query: int                  # index into the pool
    due: float                  # monotonic time the request was due
    start: float = 0.0          # when a worker sent it
    done: float = 0.0           # when its answer came back (0: never)
    matches: np.ndarray | None = None
    candidates: int = 0
    true_batches: int = 0
    error: str = ""


def _serve(send, query: tuple, rec: Record, span) -> None:
    _, op, text = query
    rec.start = time.monotonic()
    try:
        with span(QUERY_SPAN):
            res = send(op, text)
        rec.matches = np.asarray(res.matches, np.int64)
        rec.candidates = len(res.candidate_batches)
        rec.true_batches = int(res.true_batches)
        rec.done = time.monotonic()
    except Exception as e:          # a failed query is a record, not a crash
        rec.error = f"{type(e).__name__}: {e}"


def open_records(due_s, order, t0: float) -> list[Record]:
    """One record per arrival: query ``order[i]``, due at
    ``t0 + due_s[i]``.  Made before the window opens."""
    return [Record(int(q), t0 + float(d)) for d, q in zip(due_s, order)]


def open_loop(send, queries, records, *, workers: int,
              span=None) -> list[Record]:
    """Send each record's query when it is due, whatever the server
    does, through a pool of ``workers`` client threads started first;
    returns once every answer is in or ``LATE_S`` past the last
    arrival."""
    span = span or (lambda name: nullcontext())
    todo: queue.SimpleQueue = queue.SimpleQueue()

    def client() -> None:
        while (rec := todo.get()) is not None:
            _serve(send, queries[rec.query], rec, span)

    threads = [threading.Thread(target=client, name=f"bench-client-{c}",
                                daemon=True) for c in range(workers)]
    for th in threads:
        th.start()
    for rec in records:
        wait = rec.due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        todo.put(rec)
    for _ in threads:
        todo.put(None)
    deadline = time.monotonic() + LATE_S
    for th in threads:
        th.join(max(deadline - time.monotonic(), 0))
    return records


def closed_loop(send, queries, sequences, t0: float, seconds: float, *,
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
            _serve(send, queries[rec.query], rec, span)
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
