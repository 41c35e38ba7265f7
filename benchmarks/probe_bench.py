"""Beyond-paper: device-side batched sketch probing.

The paper evaluates single-threaded Java queries.  The TPU-native rethink
batches Q query tokens across S segments: throughput here is probes/sec
of the jitted device lookup vs the numpy host lookup, and of the
``bitmap_extract`` kernel (interpreted off the TPU, so off-chip numbers
are architecture-shape evidence, not TPU wall clock)."""
import time

import numpy as np

import jax
import jax.numpy as jnp


def run(results: dict):
    from repro.core.batch_builder import build_sealed
    from repro.core.immutable_sketch import build_immutable
    from repro.core.mphf import build_mphf

    rng = np.random.default_rng(0)
    n_tokens = 200_000
    fps = rng.integers(0, 2**32, n_tokens, dtype=np.uint64).astype(np.uint32)
    keys = np.unique(fps)
    mphf = build_mphf(keys)

    q = rng.integers(0, 2**32, 16384, dtype=np.uint64).astype(np.uint32)
    qj = jnp.asarray(q)

    # device lookup (jit)
    probe_jnp = jax.jit(lambda f: mphf.lookup_jnp(f))
    probe_jnp(qj)[0].block_until_ready()
    t0 = time.perf_counter()
    iters = 20
    for _ in range(iters):
        probe_jnp(qj)[0].block_until_ready()
    jnp_rate = iters * len(q) / (time.perf_counter() - t0)

    # numpy host probe (the "paper-faithful" single-core analogue)
    mphf.lookup_np(q[:2048])
    t0 = time.perf_counter()
    for _ in range(5):
        mphf.lookup_np(q)
    np_rate = 5 * len(q) / (time.perf_counter() - t0)

    # lookup_np on construction keys: every probe resolves, so the
    # vectorized residual-word rank (`_rank_np`) dominates — the
    # rank-heavy row of the host path
    present = keys[rng.integers(0, len(keys), 16384)]
    mphf.lookup_np(present[:2048])
    t0 = time.perf_counter()
    for _ in range(5):
        mphf.lookup_np(present)
    rank_rate = 5 * len(present) / (time.perf_counter() - t0)

    # bitmap_extract: device candidate compaction (bitmap -> ids) vs the
    # old host np.unpackbits expansion, bitmaps/s over a (Q, W) wave.
    # AND-query-like density (~6% of batches hit) and max_hits sized from
    # the wave's max popcount, exactly as the engine does — both sides
    # decode every hit.
    from repro.kernels import bitmap_extract
    eq, ew = 256, 64
    bm = rng.integers(0, 2**32, (eq, ew), dtype=np.uint64).astype(np.uint32)
    for _ in range(4):
        bm &= rng.integers(0, 2**32, (eq, ew), dtype=np.uint64) \
            .astype(np.uint32)
    max_pop = int(np.unpackbits(bm.view(np.uint8), axis=1).sum(axis=1).max())
    mh = 1 << (max(max_pop, 1) - 1).bit_length()
    bmj = jnp.asarray(bm)
    ext = jax.jit(lambda b: bitmap_extract(b, max_hits=mh)[0])
    ext(bmj).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        ext(bmj).block_until_ready()
    ext_rate = iters * eq / (time.perf_counter() - t0)

    def unpack_host(b):
        bits = np.unpackbits(b.view(np.uint8), axis=1, bitorder="little")
        return np.nonzero(bits)

    unpack_host(bm)
    t0 = time.perf_counter()
    for _ in range(5):
        unpack_host(bm)
    unpack_rate = 5 * eq / (time.perf_counter() - t0)

    results["probe_bench"] = dict(
        sketch_keys=int(len(keys)),
        mphf_bits_per_key=round(mphf.size_bits() / len(keys), 2),
        host_numpy_probes_per_s=round(np_rate),
        host_lookup_np_present_per_s=round(rank_rate),
        device_jnp_probes_per_s=round(jnp_rate),
        batched_speedup=round(jnp_rate / np_rate, 2),
        bitmap_extract_device_rows_per_s=round(ext_rate),
        bitmap_extract_host_unpackbits_rows_per_s=round(unpack_rate),
    )
    print(f"[probe] {len(keys)} keys, "
          f"{mphf.size_bits()/len(keys):.2f} bits/key | host "
          f"{np_rate:,.0f}/s (present {rank_rate:,.0f}/s) vs "
          f"batched-device {jnp_rate:,.0f}/s "
          f"({jnp_rate/np_rate:.1f}x)", flush=True)
    print(f"[probe] bitmap_extract ({eq}x{ew} words, max_hits {mh}): "
          f"device {ext_rate:,.0f} rows/s vs host unpackbits "
          f"{unpack_rate:,.0f} rows/s", flush=True)
