"""Compile the query path for a described TPU v5e chip, at the widths of
the 1M-line store that ``chip_smoke.py`` serves.

Nothing runs: each test lowers and compiles for a chip that is described,
not attached, so Mosaic refuses here what it would refuse on the chip
(illegal block shapes, unsupported gathers or reductions, VMEM overuse).
The shapes are those of a 1M-line store (``generate_dataset(n_lines=
1_000_000, n_sources=160, seed=2)``, ``batch_lines=512``, segmented):
1,954 batches, an engine bitmap width of 62 words, and its largest
segment's sketch arrays below.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.immutable_sketch import ImmutableSketch
from repro.core.mphf import MPHF
from repro.core.query_engine import QueryEngine
from repro.kernels.bitmap_extract.kernel import bitmap_extract_pallas
from repro.kernels.bitset_ops.kernel import bitset_reduce_batch_pallas

Q, N_BATCHES, W = 256, 1954, 62
W_PAD = 128                 # lanes: the kernels' padded word axis

# largest segment of the 1M-line store: 619,109 tokens, 186,558 lists
LEVEL_BITS = (1238272, 486912, 190976, 75776, 29696, 11776, 4608, 1792,
              768, 256, 256, 256)
LEVEL_WORD_OFFSET = (0, 38696, 53912, 59880, 62248, 63176, 63544, 63688,
                     63744, 63768, 63776, 63784, 63792)
SEG_SHAPES = {
    "words": ((63792,), jnp.uint32), "block_rank": ((7974,), jnp.uint32),
    "level_word_offset": ((13,), jnp.int32),
    "level_bits": ((12,), jnp.int32),
    "fallback_fps": ((1,), jnp.uint32), "fallback_idx": ((1,), jnp.int32),
    "fb_count": ((), jnp.int32),
    "csf_bitseq": ((212640,), jnp.uint32),
    "csf_lengths": ((96736,), jnp.uint32),
    "csf_samples": ((19348,), jnp.int32),
    "signatures": ((154778,), jnp.uint32), "n_tokens1": ((), jnp.int32),
    "planes": ((186558, 49), jnp.uint32), "n_lists1": ((), jnp.int32),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("t", [1, 8])
def test_bitset_batch_compiles(one_chip, t):
    fn = jax.jit(lambda p: bitset_reduce_batch_pallas(
        p, op="and", block_q=Q, block_w=W_PAD, interpret=False))
    compiled = fn.lower(_spec((Q, t, W_PAD), jnp.uint32, one_chip)).compile()
    assert _has_kernel(compiled)


def test_bitmap_extract_compiles(one_chip):
    """Full bucket: every batch of the store can be a hit."""
    n_slots = 1 << (N_BATCHES - 1).bit_length()          # 2048
    fn = jax.jit(lambda b: bitmap_extract_pallas(
        b, n_slots=n_slots, interpret=False))
    compiled = fn.lower(_spec((Q, W_PAD), jnp.int32, one_chip)).compile()
    assert _has_kernel(compiled)


def test_mphf_probe_compiles(one_chip):
    """The device MPHF lookup at a 1M-line segment's word and rank
    directory sizes, over a full wave's Q*T fingerprints."""
    from repro.core.mphf import lookup_arrs
    names = ("words", "block_rank", "fallback_fps", "fallback_idx",
             "fb_count")
    arrs = {k: _spec(*SEG_SHAPES[k], one_chip) for k in names}
    fn = jax.jit(lambda f, a: lookup_arrs(
        f, a, level_bits=LEVEL_BITS, level_word_offset=LEVEL_WORD_OFFSET))
    fn.lower(_spec((Q * 8,), jnp.uint32, one_chip), arrs).compile()


def test_segment_probe_compiles(one_chip):
    """One whole per-segment probe as the engine jits it (MPHF lookup,
    signature check, CSF rank, plane gather, pad to the engine width)."""
    mphf = MPHF(words=np.zeros(0, np.uint32),
                level_word_offset=np.asarray(LEVEL_WORD_OFFSET, np.int32),
                level_bits=np.asarray(LEVEL_BITS, np.int32),
                block_rank=np.zeros(0, np.uint32),
                fallback_fps=np.zeros(0, np.uint32),
                fallback_idx=np.zeros(0, np.int64),
                n_keys=619109, n_rank_bits=619109)
    seg = ImmutableSketch(
        mphf=mphf, csf=None, signatures=np.zeros(0, np.uint32), sig_bits=8,
        bic_bits=np.zeros(0, np.uint32), bic_offsets=np.zeros(1, np.int64),
        bic_counts=np.zeros(0, np.int64), n_postings=N_BATCHES,
        n_tokens=619109, planes=np.zeros((1, 49), np.uint32))
    engine = QueryEngine([seg], n_postings=N_BATCHES)
    assert engine.words == W
    arrs = {k: _spec(*v, one_chip) for k, v in SEG_SHAPES.items()}
    lowered = engine._seg_fn(0).lower(_spec((Q, 8), jnp.uint32, one_chip),
                                      arrs)
    assert lowered.out_info.shape == (Q, 8, W)
    lowered.compile()
