"""The check that decides ``correct``, shown to fail.

Each test drives a whole run of a cell (corpus, cached store, serving,
warm-up, window, reference, check) on the CPU at a size a test run can
hold, skipping only the harness's look for a chip.  A sound run is
correct; the control and each fault the cells can have are not:

  * control: the store's guarantee broken the way a faster post-filter
    would tempt, by answering with every line of every candidate batch
    instead of the lines that match;
  * half of each wave left out: the engine returns nothing for every
    other query of a wave;
  * an answer altered where it is produced: the post-filter drops the
    last line it found.
"""
import numpy as np
import pytest

from bench import harness, store_cache
from bench.control import every_line_of_candidates
from bench.sweep import sweep_bench

TINY = {"corpus": {"n_lines": 8000, "n_sources": 24},
        "store": {"batch_lines": 128},
        "serving": {"bucket_sizes": [8], "n_replicas": 1}}
OVERRIDES = {
    "loghub-1m.ioc": {
        "config": TINY,
        "traffic": {"rate_qps": 30, "workers": 8, "small_sources": 12,
                    "pool": {"term_id": 20, "present_term_id": 4,
                             "term_ip": 20, "present_term_ip": 4}}},
    "loghub-1m.hunt": {
        "config": TINY,
        "traffic": {"extracted_lines": 200,
                    "pool": {"contains_ip": 2, "term_extracted": 2,
                             "contains_id": 2}}},
}


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    import repro.compile_cache
    monkeypatch.setattr(store_cache, "CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: None)


def _bench(cell):
    """BENCHMARK.json, or for a mix that is not a cell of it yet (the
    open-loop ioc mix, whose rate awaits its sweep) the sweep's one-cell
    benchmark."""
    bench = harness.load_benchmark()
    if any(w["name"] == cell for w in bench["workloads"]):
        return bench
    return sweep_bench(*cell.split("."))[1]


def _run(cell, fault=None, seconds=1.5):
    import jax
    return harness.run_cell(cell, 4, seconds, False, jax.devices()[:1],
                            0.0, bench=_bench(cell),
                            overrides=OVERRIDES[cell], fault=fault)


def _drop_last_match(store):
    exact = store._post_filter

    def post_filter(candidates, term, mode):
        res = exact(candidates, term, mode)
        res.matches = res.matches[:-1]
        return res
    store._post_filter = post_filter


@pytest.mark.parametrize("cell", sorted(OVERRIDES))
def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert "queries_per_s" in out["metrics"]


@pytest.mark.parametrize("cell", sorted(OVERRIDES))
def test_control_is_not_correct(cell):
    out = _run(cell, fault=every_line_of_candidates)
    assert not out["correct"]
    assert out["checks"]["answers_differing"]["value"] > 0


def test_half_of_each_wave_left_out_is_not_correct(monkeypatch):
    from repro.core.query_engine import QueryEngine
    whole = QueryEngine.query_fps_batch

    def half(self, fps_lists, *, op="and"):
        res = whole(self, fps_lists, op=op)
        return [r if i % 2 == 0 else np.empty(0, np.int64)
                for i, r in enumerate(res)]
    monkeypatch.setattr(QueryEngine, "query_fps_batch", half)
    out = _run("loghub-1m.hunt")
    assert not out["correct"]


def test_answer_altered_where_produced_is_not_correct():
    out = _run("loghub-1m.hunt", fault=_drop_last_match)
    assert not out["correct"]


def test_ioc_answer_altered_where_produced_is_not_correct():
    out = _run("loghub-1m.ioc", fault=_drop_last_match)
    assert not out["correct"]
