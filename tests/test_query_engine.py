"""Batched device query engine: bit-exact equivalence with the host
Alg. 3 loop, one-upload-per-segment device caching, one-compile-per-
bucket-shape jit behaviour, and multi-segment / plane-less fan-out."""
import numpy as np
import pytest

from repro.core.batch_builder import build_sealed
from repro.core.immutable_sketch import ImmutableSketch, build_immutable
from repro.core.query import query_and, query_or
from repro.core.query_engine import _MIN_HITS_BUCKET, QueryEngine, _bucket
from repro.core.segment import SegmentWriter


def _corpus(seed, n_tokens=250, n_postings=48, n_pairs=3000):
    rng = np.random.default_rng(seed)
    fps = (rng.integers(0, n_tokens, n_pairs).astype(np.uint64)
           * 2654435761 % (1 << 32)).astype(np.uint32)
    posts = rng.integers(0, n_postings, n_pairs).astype(np.int64)
    return rng, fps, posts


def _random_queries(rng, uniq, n=24, t_max=6):
    """Mix of present tokens, absent tokens, and empty queries."""
    queries = [[]]
    for _ in range(n):
        t = int(rng.integers(1, t_max + 1))
        q = [int(x) for x in rng.choice(uniq, size=min(t, len(uniq)),
                                        replace=False)]
        if rng.random() < 0.4:  # inject an absent fingerprint
            q[rng.integers(0, len(q))] = int(rng.integers(0, 2**32))
        queries.append(q)
    return queries


# -------------------------------------------------------------- equivalence
@pytest.mark.parametrize("seed", [0, 2])
def test_engine_matches_host_single_segment(seed):
    rng, fps, posts = _corpus(seed)
    sk = build_immutable(build_sealed(fps, posts))
    eng = QueryEngine([sk])
    queries = _random_queries(rng, np.unique(fps))
    for op, ref in (("and", query_and), ("or", query_or)):
        got = eng.query_fps_batch(queries, op=op)
        for q, g in zip(queries, got):
            want = ref(sk, q) if q else np.empty(0, np.int64)
            np.testing.assert_array_equal(g, want), (op, q)


@pytest.mark.parametrize("plane_budget", [64 << 20, 0])
def test_engine_matches_host_multi_segment(plane_budget):
    """Per-spill segments (with and without bitmap planes) OR their
    per-token bitmaps; results equal the same-segment host oracle."""
    rng, fps, posts = _corpus(7, n_pairs=6000, n_postings=60)
    w = SegmentWriter(memory_limit_bytes=1 << 12,
                      plane_budget_bytes=plane_budget)
    for f, p in zip(fps, posts):
        w.add_fingerprints(np.asarray([f], np.uint32), int(p))
    segs = w.finish_segments()
    assert len(segs) > 1, "corpus must spill into multiple segments"
    if plane_budget == 0:
        assert all(s.planes is None for s in segs)
    eng = QueryEngine(segs, n_postings=60)
    queries = _random_queries(rng, np.unique(fps), n=20)
    for op in ("and", "or"):
        got = eng.query_fps_batch(queries, op=op)
        for q, g in zip(queries, got):
            np.testing.assert_array_equal(g, eng.host_query(q, op=op))


def test_multi_segment_union_equals_monolithic_for_present_tokens():
    """For construction-set tokens (no signature false positives) the
    fan-out result must equal the merged monolithic sketch's result."""
    rng, fps, posts = _corpus(11, n_pairs=5000)
    w = SegmentWriter(memory_limit_bytes=1 << 12)
    for f, p in zip(fps, posts):
        w.add_fingerprints(np.asarray([f], np.uint32), int(p))
    segs = w.finish_segments()
    assert len(segs) > 1
    mono = build_immutable(build_sealed(fps, posts))
    eng = QueryEngine(segs, n_postings=mono.n_postings)
    uniq = np.unique(fps)
    queries = [[int(x) for x in rng.choice(uniq, 3, replace=False)]
               for _ in range(16)]
    for op, ref in (("and", query_and), ("or", query_or)):
        got = eng.query_fps_batch(queries, op=op)
        for q, g in zip(queries, got):
            np.testing.assert_array_equal(g, ref(mono, q))


# ------------------------------------------------------------ device cache
def test_one_device_upload_per_segment(monkeypatch):
    _, fps, posts = _corpus(3)
    sk = build_immutable(build_sealed(fps, posts))
    calls = {"n": 0}
    orig = ImmutableSketch.device_arrays

    def counting(self):
        calls["n"] += 1
        return orig(self)

    monkeypatch.setattr(ImmutableSketch, "device_arrays", counting)
    eng = QueryEngine([sk])
    uniq = np.unique(fps)
    for _ in range(5):  # many waves, several shapes
        eng.query_fps_batch([[int(uniq[0])], [int(uniq[1]), int(uniq[2])]])
        eng.query_fps_batch([[int(x) for x in uniq[:5]]], op="or")
    assert calls["n"] == 1, "segment arrays must upload exactly once"
    assert eng.upload_count == 1

    # a second engine over the same sketch reuses the process-wide cache
    eng2 = QueryEngine([sk])
    eng2.query_fps_batch([[int(uniq[0])]])
    assert calls["n"] == 1


# ---------------------------------------------------------------- jit cache
def _hits_bucket(results) -> int | None:
    """The extract program's ``max_hits`` bucket of a wave's answers
    (None: no answer has a hit, so the wave runs no extract)."""
    most = max(len(r) for r in results)
    return _bucket(most, _MIN_HITS_BUCKET) if most else None


def test_one_compile_per_bucket_shape():
    """The probe and the reduce trace once per (Q, T) bucket, the extract
    once per hit-count bucket; a repeated shape traces nothing."""
    _, fps, posts = _corpus(4)
    sk = build_immutable(build_sealed(fps, posts))
    eng = QueryEngine([sk])
    uniq = [int(x) for x in np.unique(fps)[:40]]
    seen: set[int] = set()

    def wave(queries) -> int:
        """Traces of one wave, less one for an extract bucket not seen."""
        before = eng.compile_count
        hits = _hits_bucket(eng.query_fps_batch(queries))
        fresh = hits is not None and hits not in seen
        seen.add(hits)
        return eng.compile_count - before - fresh

    assert wave([uniq[:1]]) == 2             # bucket (8, 1): probe, reduce
    for _ in range(4):                       # same bucket -> no retrace
        assert wave([uniq[1:2], uniq[2:3]]) == 0
    assert wave([uniq[:3]]) == 2             # bucket (8, 4): +probe +reduce
    for _ in range(3):
        assert wave([uniq[3:6], uniq[6:9]]) == 0


def test_extract_traces_are_counted():
    """A wave whose answers reach a new hit-count bucket traces one more
    program, the extract, and counts it; the same bucket again does not."""
    _, fps, posts = _corpus(4)
    sk = build_immutable(build_sealed(fps, posts))
    eng = QueryEngine([sk])
    uniq = [int(x) for x in np.unique(fps)]
    by_bucket: dict[int, int] = {}
    for fp in uniq:
        hits = _hits_bucket(eng.query_fps_batch([[fp]]))
        if hits is not None:
            by_bucket.setdefault(hits, fp)
        if len(by_bucket) == 2:
            break
    assert len(by_bucket) == 2, "the corpus needs two hit-count buckets"
    small, large = sorted(by_bucket.items())
    eng = QueryEngine([sk])
    eng.query_fps_batch([[small[1]]])        # probe, reduce, extract
    assert eng.compile_count == 3
    eng.query_fps_batch([[large[1]]])        # the same (Q, T): extract only
    assert eng.compile_count == 4
    eng.query_fps_batch([[small[1]]])
    eng.query_fps_batch([[large[1]]])
    assert eng.compile_count == 4


# ------------------------------------------------------------- store level
def test_segmented_store_equals_batch_store(small_dataset):
    from repro.logstore.datasets import present_id_queries
    from repro.logstore.store import DynaWarpStore
    a = DynaWarpStore(batch_lines=64, mode="batch")
    b = DynaWarpStore(batch_lines=64, mode="segmented",
                      memory_limit_bytes=1 << 16)
    for s in (a, b):
        s.ingest(small_dataset.lines)
        s.finish()
    assert len(b.segments) > 1, "segmented store must keep spills"
    queries = present_id_queries(small_dataset, 3, 6) + ["info", "gc"]
    for t in queries:
        assert a.query_term(t).matches == b.query_term(t).matches, t
        assert (a.query_contains(t[2:10]).matches
                == b.query_contains(t[2:10]).matches), t


def test_store_batch_api_matches_sequential(small_dataset):
    from repro.logstore.datasets import id_queries, present_id_queries
    from repro.logstore.store import DynaWarpStore
    s = DynaWarpStore(batch_lines=64)
    s.ingest(small_dataset.lines)
    s.finish()
    terms = present_id_queries(small_dataset, 5, 4) + id_queries(13, 4)
    batch = s.query_term_batch(terms)
    for t, r in zip(terms, batch):
        seq = s.query_term(t)
        assert r.matches == seq.matches
        np.testing.assert_array_equal(np.sort(r.candidate_batches),
                                      np.sort(seq.candidate_batches))


def test_store_device_query_off_matches_on(small_dataset):
    from repro.logstore.datasets import present_id_queries
    from repro.logstore.store import DynaWarpStore
    on = DynaWarpStore(batch_lines=64, device_query=True)
    off = DynaWarpStore(batch_lines=64, device_query=False)
    for s in (on, off):
        s.ingest(small_dataset.lines)
        s.finish()
    assert on.engine is not None and off.engine is None
    for t in present_id_queries(small_dataset, 9, 5):
        np.testing.assert_array_equal(np.sort(on.candidates_term(t)),
                                      np.sort(off.candidates_term(t)))
