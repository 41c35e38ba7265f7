"""A deployment that arrives as new files: a corpus generator under
``bench/corpora/``, a query scenario with an op of its own under
``bench/queries/``, and a config's ``open`` settings, each driven
through ``harness.run_cell`` on the CPU (the harness's look for a chip
skipped).  The parts live in a temporary benchmark directory, which the
harness finds in place of ``bench/``; no config of them ships."""
import json

import pytest

from bench import byname, harness, store_cache

GENERATOR = '''
import numpy as np

from bench.corpus import Corpus


def generate(*, n_lines, n_sources, seed):
    rng = np.random.default_rng(seed)
    sources = np.sort(rng.integers(0, n_sources, n_lines)).astype(np.int32)
    keys = rng.integers(0, 400, n_lines).tolist()
    lines = [f"tenant{s} GET /kv/key{k} status={404 if k % 7 == 0 else 200}"
             for s, k in zip(sources.tolist(), keys)]
    return Corpus(lines=lines, sources=sources)
'''

#: Lines that hold either of two keys as a term: an op the harness does
#: not know, served as one batch of two term queries.
SCENARIO = '''
from types import SimpleNamespace

import numpy as np

from bench.reference import line_terms

OP = "either"
DROP = {drop}


def draw(rng, corpus, n, traffic):
    pairs = rng.choice(500, size=(4 * n, 2)).tolist()
    texts = [f"key{{a}}|key{{b}}" for a, b in pairs if a != b]
    return list(dict.fromkeys(texts))[:n]


def tokens(text):
    from repro.core.tokenizer import term_query_tokens
    return term_query_tokens(text.split("|")[0])


def send(server, text, timeout):
    ra, rb = server.query_term_batch(text.split("|"), timeout=timeout)
    return SimpleNamespace(
        matches=sorted(set(ra.matches) | set(rb.matches)),
        candidate_batches=np.union1d(ra.candidate_batches,
                                     rb.candidate_batches),
        true_batches=ra.true_batches + rb.true_batches)


def answer(lines, texts):
    out = {{}}
    for t in texts:
        keys = set(t.split("|"))
        out[t] = [i for i, line in enumerate(lines)
                  if keys & line_terms(line.lower())]
    if DROP:
        t = next(t for t in texts if out[t])
        out[t] = out[t][:-1]
    return out
'''

CONFIG = {
    "corpus": {"generator": "kv_lines", "n_lines": 6000, "n_sources": 8,
               "seed": 5},
    "store": {"batch_lines": 128, "mode": "segmented"},
    "serving": {"n_replicas": 1, "bucket_sizes": [8],
                "flush_deadline_s": 0.002},
}
TRAFFIC = {"loop": "closed", "clients": 2, "sequence_length": 64,
           "pool": {"either": 6, "term_id": 2}}


@pytest.fixture
def bench_dir(tmp_path, monkeypatch):
    import repro.compile_cache
    for kind, name, text in (
            ("corpora", "kv_lines", GENERATOR),
            ("queries", "either", SCENARIO.format(drop=False)),
            ("queries", "either_dropping", SCENARIO.format(drop=True))):
        (tmp_path / kind).mkdir(exist_ok=True)
        (tmp_path / kind / f"{name}.py").write_text(text)
    for kind in ("configs", "traffic"):
        (tmp_path / kind).mkdir()
    monkeypatch.setattr(byname, "BENCH_DIR", str(tmp_path))
    monkeypatch.setattr(harness, "BENCH_DIR", str(tmp_path))
    monkeypatch.setattr(store_cache, "CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: None)
    return tmp_path


def _run(bench_dir, config=CONFIG, traffic=TRAFFIC, fault=None):
    import jax
    (bench_dir / "configs" / "kv.json").write_text(json.dumps(config))
    (bench_dir / "traffic" / "mix.json").write_text(json.dumps(traffic))
    bench = {"workloads": [{"name": "kv.mix", "config": "kv",
                            "traffic": "mix", "chips": 1}],
             "end_to_end": [{"name": "queries_per_s", "unit": "queries/s"}],
             "per_layer": []}
    return harness.run_cell("kv.mix", 6, 1.5, False, jax.devices()[:1],
                            0.0, bench=bench, fault=fault)


def test_generator_and_scenario_found_by_name_are_correct(bench_dir):
    seen = {}

    def look(store):
        seen["n_lines"] = store.batch_start[-1]
    out = _run(bench_dir, fault=look)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert seen["n_lines"] == CONFIG["corpus"]["n_lines"]


def test_scenario_answer_dropping_an_id_is_not_correct(bench_dir):
    traffic = {**TRAFFIC, "pool": {"either_dropping": 6, "term_id": 2}}
    out = _run(bench_dir, traffic=traffic)
    assert not out["correct"]
    assert out["checks"]["answers_differing"]["value"] > 0


def test_open_settings_reach_the_store(bench_dir):
    from repro.core.distributed import ShardedQueryEngine
    seen = {}

    def look(store):
        seen["axes"] = store.shard_axes
        seen["engine"] = type(store.engine)
    config = {**CONFIG, "open": {"shard_axes": ["data"]}}
    out = _run(bench_dir, config=config, fault=look)
    assert seen == {"axes": ("data",), "engine": ShardedQueryEngine}
    assert out["correct"], out["checks"]
