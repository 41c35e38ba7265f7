"""Per-kernel shape/dtype sweeps: Pallas (interpreted off the TPU) vs the
pure-jnp oracles in each kernel's ref.py."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.kernels import (bitmap_extract, bitset_reduce,
                           bitset_reduce_batch, csc_partition_mask,
                           embedding_bag_sum, retrieval_scores,
                           token_fingerprints)
from repro.kernels.bitmap_extract.ref import bitmap_extract_ref
from repro.kernels.bitset_ops.ref import (bitset_reduce_batch_ref,
                                          bitset_reduce_ref)
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.retrieval_score.ref import retrieval_score_ref
from repro.kernels.token_hash.ref import token_hash_ref


@pytest.mark.parametrize("n,l", [(8, 4), (100, 24), (1025, 32), (4096, 16)])
def test_token_hash_shapes(n, l, rng):
    toks = rng.integers(0, 256, (n, l)).astype(np.uint8)
    lens = rng.integers(0, l + 1, n).astype(np.int32)
    for i in range(n):
        toks[i, lens[i]:] = 0
    got = token_fingerprints(jnp.asarray(toks), jnp.asarray(lens))
    want = token_hash_ref(jnp.asarray(toks), jnp.asarray(lens))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("t,w,op", [(1, 64, "and"), (3, 700, "and"),
                                    (8, 2048, "or"), (16, 513, "and")])
def test_bitset_shapes(t, w, op, rng):
    planes = rng.integers(0, 2**32, (t, w), dtype=np.uint64) \
        .astype(np.uint32)
    c, n = bitset_reduce(jnp.asarray(planes), op=op)
    cr, nr = bitset_reduce_ref(jnp.asarray(planes), op=op)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))
    assert int(n) == int(nr)


@pytest.mark.parametrize("q,t,w,op", [(1, 1, 10, "and"), (4, 3, 700, "and"),
                                      (8, 8, 513, "or"), (512, 1, 40, "and"),
                                      (16, 2, 64, "or")])
def test_bitset_batch_shapes(q, t, w, op, rng):
    planes = rng.integers(0, 2**32, (q, t, w), dtype=np.uint64) \
        .astype(np.uint32)
    c, n = bitset_reduce_batch(jnp.asarray(planes), op=op)
    cr, nr = bitset_reduce_batch_ref(jnp.asarray(planes), op=op)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(n), np.asarray(nr))


@pytest.mark.parametrize("q,w,max_hits", [(1, 1, 8), (8, 4, 16),
                                          (16, 33, 64), (5, 7, 4),
                                          (9, 200, 700)])
def test_bitmap_extract_shapes(q, w, max_hits, rng):
    """Kernel vs jnp ref vs a numpy oracle, including the truncation
    path (max_hits smaller than a row's popcount)."""
    bm = rng.integers(0, 2**32, (q, w), dtype=np.uint64).astype(np.uint32)
    bm[0] = 0                                   # an empty row
    k_ids, k_cnt = bitmap_extract(jnp.asarray(bm), max_hits=max_hits)
    r_ids, r_cnt = bitmap_extract_ref(jnp.asarray(bm), max_hits=max_hits)
    np.testing.assert_array_equal(np.asarray(k_ids), np.asarray(r_ids))
    np.testing.assert_array_equal(np.asarray(k_cnt), np.asarray(r_cnt))
    for i in range(q):
        want = np.flatnonzero(np.unpackbits(bm[i].view(np.uint8),
                                            bitorder="little"))
        got = np.asarray(k_ids[i])
        np.testing.assert_array_equal(got[got >= 0], want[:max_hits])
        assert int(k_cnt[i]) == want.size


@pytest.mark.parametrize("nkeys", [50, 1000, 20000])
def test_mphf_probe_sweep(nkeys, rng):
    """The device MPHF lookup (jitted, as the engine runs it) against
    the host numpy lookup: every construction key resolves to the same
    minimal hash, and the absent flags agree on random probes."""
    from repro.core.mphf import build_mphf, lookup_arrs
    keys = np.unique(rng.integers(0, 2**32, nkeys, dtype=np.uint64)
                     .astype(np.uint32))
    m = build_mphf(keys)
    q = np.concatenate([keys, rng.integers(0, 2**32, 777, dtype=np.uint64)
                        .astype(np.uint32)])
    layout = dict(level_bits=tuple(int(x) for x in m.level_bits),
                  level_word_offset=tuple(int(x)
                                          for x in m.level_word_offset))
    ki, ka = jax.jit(lambda f, a: lookup_arrs(f, a, **layout))(
        jnp.asarray(q), m.device_arrays())
    ri, ra = m.lookup_np(q)
    np.testing.assert_array_equal(np.asarray(ka), ra)
    keep = ~ra
    np.testing.assert_array_equal(np.asarray(ki)[keep], ri[keep])
    np.testing.assert_array_equal(np.sort(np.asarray(ki)[:keys.size]),
                                  np.arange(keys.size))


@pytest.mark.parametrize("m_bits,k,p,j", [(1 << 12, 2, 16, 1),
                                          (1 << 16, 4, 64, 2)])
def test_csc_probe_sweep(m_bits, k, p, j, rng):
    from repro.baselines.csc import CSCSketch
    sk = CSCSketch.build(m_bits=m_bits, k=k, p=p, j=j, n_sets=50)
    fps = rng.integers(0, 2**32, 1500, dtype=np.uint64).astype(np.uint32)
    sk.insert_batch(fps, rng.integers(0, 50, 1500))
    q = np.concatenate([fps[:100], rng.integers(0, 2**32, 64,
                                                dtype=np.uint64)
                        .astype(np.uint32)])
    got = csc_partition_mask(sk, q)
    want = sk.partition_mask_jnp(jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("v,d,b,bag,dtype", [
    (100, 8, 8, 2, np.float32), (1000, 32, 64, 8, np.float32),
    (500, 128, 16, 4, np.float32)])
def test_embedding_bag_sweep(v, d, b, bag, dtype, rng):
    table = rng.normal(size=(v, d)).astype(dtype)
    idx = rng.integers(0, v, (b, bag)).astype(np.int32)
    got = embedding_bag_sum(jnp.asarray(table), jnp.asarray(idx))
    want = embedding_bag_ref(jnp.asarray(table), jnp.asarray(idx))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("c,d", [(256, 32), (5000, 64), (10000, 256)])
def test_retrieval_score_sweep(c, d, rng):
    corpus = rng.normal(size=(c, d)).astype(np.float32)
    q = rng.normal(size=(d,)).astype(np.float32)
    got = retrieval_scores(jnp.asarray(corpus), jnp.asarray(q))
    want = retrieval_score_ref(jnp.asarray(corpus), jnp.asarray(q)[None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-4)


@given(st.integers(1, 300), st.integers(1, 12))
@settings(max_examples=10, deadline=None)
def test_token_hash_property(n, l):
    rng = np.random.default_rng(n * 31 + l)
    toks = rng.integers(0, 256, (n, l)).astype(np.uint8)
    lens = rng.integers(0, l + 1, n).astype(np.int32)
    for i in range(n):
        toks[i, lens[i]:] = 0
    got = token_fingerprints(jnp.asarray(toks), jnp.asarray(lens))
    want = token_hash_ref(jnp.asarray(toks), jnp.asarray(lens))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,s,hq,hkv,d,clen,dtype", [
    (2, 128, 4, 2, 16, 100, np.float32),
    (1, 700, 8, 8, 32, 650, np.float32),
    (4, 64, 16, 2, 8, 64, np.float32),
    (2, 256, 6, 3, 64, 17, np.float32),
])
def test_flash_decode_sweep(b, s, hq, hkv, d, clen, dtype, rng):
    from repro.kernels import flash_decode
    from repro.kernels.flash_decode.ref import flash_decode_ref
    q = jnp.asarray(rng.normal(size=(b, hq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    got = flash_decode(q, k, v, jnp.int32(clen), block_s=64)
    want = flash_decode_ref(q, k, v, jnp.int32(clen))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
