"""Load schedules: open-loop arrivals and closed-loop client sequences.

The open-loop arithmetic follows ``benchmarks/serve_load.py``: arrivals
come at exponential gaps whatever the server does, and each request is
timed from the moment it was due, so a stall is charged to every request
it delays.  Here the gaps are scaled so that exactly ``rate * seconds``
arrivals fall inside the window: a Poisson process conditioned on its
count.  Every seed then offers the same number of requests, in another
order and at other instants.
"""
from __future__ import annotations

import numpy as np


def open_schedule(n_pool: int, rate: float, seconds: float, seed: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """``(due_s, query_index)``: arrival offsets in ``[0, seconds)`` and
    the pool entry each arrival sends.  The pool is cycled in a seeded
    order, so every entry is sent equally often (to within one)."""
    rng = np.random.default_rng([seed, 2])
    n = int(round(rate * seconds))
    gaps = rng.exponential(1.0, size=n + 1)
    due = np.cumsum(gaps)[:n] / gaps.sum() * seconds
    order = np.concatenate([rng.permutation(n_pool)
                            for _ in range(-(-n // n_pool))])[:n]
    return due, order


def closed_sequences(scenario_of: list[str], clients: int, length: int,
                     seed: int) -> list[list[int]]:
    """One sequence of pool indices per client.  Each client visits the
    scenarios in turn (so each takes an equal share) and each
    scenario's queries in a seeded order of its own."""
    rng = np.random.default_rng([seed, 3])
    by_scenario: dict[str, list[int]] = {}
    for i, s in enumerate(scenario_of):
        by_scenario.setdefault(s, []).append(i)
    names = list(by_scenario)
    seqs = []
    for c in range(clients):
        perms = {s: rng.permutation(by_scenario[s]).tolist() for s in names}
        seq = []
        for k in range(length):
            s = names[(k + c) % len(names)]
            p = perms[s]
            seq.append(p[(k // len(names)) % len(p)])
        seqs.append(seq)
    return seqs
