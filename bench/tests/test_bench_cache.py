"""The store and answer caches' keys, and the per-layer readers that
must not fall silent when a kernel's op is missing from a trace."""
import copy
import json
import os

import numpy as np
import pytest

from bench import harness, loops, store_cache, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "bench", "configs", "loghub-1m.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("section,key,value,store_moves,answers_move", [
    ("corpus", "n_sources", 161, True, True),
    ("corpus", "seed", 3, True, True),
    ("store", "batch_lines", 256, True, False),
    ("serving", "n_replicas", 1, False, False),
])
def test_cache_keys_cover_the_settings(cfg, section, key, value,
                                       store_moves, answers_move):
    other = copy.deepcopy(cfg)
    other[section][key] = value
    assert (store_cache.store_key(other) != store_cache.store_key(cfg)) \
        == store_moves
    assert (store_cache.answers_key(other) != store_cache.answers_key(cfg)) \
        == answers_move


def test_a_matching_key_reopens_the_store(cfg, tmp_path, monkeypatch):
    from bench import corpus as bench_corpus
    monkeypatch.setattr(store_cache, "CACHE_DIR", str(tmp_path))
    small = {**cfg, "corpus": {**cfg["corpus"], "n_lines": 3000,
                               "n_sources": 12},
             "store": {**cfg["store"], "batch_lines": 128}}
    built = []

    def lines():
        built.append(1)
        return bench_corpus.generate(**small["corpus"]).lines
    first = store_cache.store_path("t", small, lines)
    again = store_cache.store_path("t", small, lines)
    assert first == again and built == [1]
    changed = {**small, "store": {**small["store"], "batch_lines": 64}}
    other = store_cache.store_path("t", changed, lines)
    assert other != first and built == [1, 1]
    assert not os.path.exists(first)      # one store per configuration


def _run(events, device_waves=3, candidates=2):
    recs = [loops.Record(0, 0.0, done=1.0, matches=np.arange(1),
                         candidates=candidates)]
    t = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            [trace.WINDOW_SPAN, 0.0, 1e7]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": trace.OPS_LINE, "events": events}]}]}
    return harness.Run(cell="c", config={}, traffic={},
                       queries=[("term_id", "term", "x")], n_tokens=[1],
                       records=recs, stats={"device_waves": device_waves,
                                            "completed": 1},
                       n_batches=1954, device_kind="TPU v5 lite", t0=0.0,
                       t_end=1.0, trace=t)


@pytest.mark.parametrize("metric,op", [
    ("bitset_ops_roofline", "bitset_reduce_batch_pallas.2"),
    ("bitmap_extract_roofline", "bitmap_extract_pallas.1"),
])
def test_roofline_readers(metric, op):
    read = harness.load_reader(metric)
    share = read(_run([[op, 1e6, 1e3], ["fusion.3", 2e6, 1e3]]))
    assert 0 < share < 100
    with pytest.raises(RuntimeError, match="op name"):
        read(_run([["fusion.3", 2e6, 1e3]]))
    assert read(_run([["fusion.3", 2e6, 1e3]], device_waves=0)) is None
    no_trace = _run([])
    no_trace.trace = None
    assert read(no_trace) is None
