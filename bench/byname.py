"""The benchmark's parts that are found by a name: ``bench/<kind>/<name>.py``.

A kind is a directory of the benchmark: ``metrics`` (per-layer readers),
``corpora`` (corpus generators) or ``queries`` (query scenarios).  A
later deployment adds a file there and names it in ``BENCHMARK.json``
or in its config or traffic file; no existing file changes.
"""
from __future__ import annotations

import importlib.util
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def path(kind: str, name: str) -> str:
    return os.path.join(BENCH_DIR, kind, f"{name}.py")


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, executed anew."""
    p = path(kind, name)
    if not os.path.exists(p):
        raise SystemExit(f"bench: no bench/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
