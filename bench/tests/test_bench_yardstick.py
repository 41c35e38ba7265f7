"""The harness's plug points did not move the yardstick: ``loghub-1m``'s
lines and the query pools of its traffic mixes are pinned by digest.

The digests were computed at commit c8aec61, before corpus generators,
query scenarios and store-open settings became files found by name, by
running ``bench.corpus.generate`` and ``bench.scenarios.pool`` on
``bench/configs/loghub-1m.json``'s corpus settings with ``n_lines``
20,000.  A pool is digested as the JSON of its ``(scenario, op, text)``
triples, in order.
"""
import hashlib
import json
import os

import pytest

from bench import harness, scenarios

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
N_LINES = 20_000
LINES_SHA256 = \
    "e29d04fee22b88c02e34cfd92fcaf7668c04e4daabd47189a2b0346ac6bc1134"
POOL_SHA256 = {
    ("hunt", 3):
        "6c129b2498cfa7c843dea75bc8ed751f51bb0d0c59c052a840dfac3969c74c8c",
    ("hunt", 4):
        "7190c78668ca00f9b859c6173b323285fd25de69259da8a5f69178433a01684f",
    ("ioc", 3):
        "70562ae7c4956d196f0fe66376b67f7ac5e5732480a4bdf1143b1bc452e6e24c",
    ("ioc", 4):
        "e09ee2ee3abab80745e1b0166b0b6563545bfff544c9c50c9f202368dc3fdd17",
}


def _load(kind, name):
    with open(os.path.join(ROOT, "bench", kind, f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def corpus():
    cfg = _load("configs", "loghub-1m")
    cfg["corpus"]["n_lines"] = N_LINES
    return harness.make_corpus(cfg)


def test_loghub_lines_are_pinned(corpus):
    h = hashlib.sha256()
    for line in corpus.lines:
        h.update(line.encode())
        h.update(b"\n")
    assert h.hexdigest() == LINES_SHA256


def _digest(pool) -> str:
    return hashlib.sha256(json.dumps(pool).encode()).hexdigest()


@pytest.mark.parametrize("traffic,seed", sorted(POOL_SHA256))
def test_pools_are_pinned(corpus, traffic, seed):
    """Each seed's draw, as the parent drew it (a traffic's
    ``pool_seed``, which came later, left out)."""
    t = _load("traffic", traffic)
    t.pop("pool_seed", None)
    pool, _ = scenarios.pool(t, corpus, seed)
    assert _digest(pool) == POOL_SHA256[(traffic, seed)]


@pytest.mark.parametrize("seed", [3, 4, 2**31 + 5])
def test_ioc_pool_is_the_same_for_every_seed(corpus, seed):
    """``ioc.json``'s ``pool_seed`` 3 gives every seed seed 3's pool, so
    a seed changes only the order and the instants of the arrivals."""
    pool, _ = scenarios.pool(_load("traffic", "ioc"), corpus, seed)
    assert _digest(pool) == POOL_SHA256[("ioc", 3)]
