"""Pallas TPU kernel: posting-bitmap boolean algebra (query intersection).

Query-time search-space reduction (paper Alg. 3 consumer): with posting
lists materialized as dense bit planes over the S data batches, an
AND-query over T tokens is a reduction over T u32 planes followed by a
popcount.  This is pure VPU work: each grid step loads a
(block_q, T, block_w) tile, folds AND (or OR) across the T axis, writes
the combined (block_q, block_w) plane, and adds the tile's popcount into
the (block_q, 1) counts block, which stays resident across the word axis
(the innermost grid axis) and is zeroed at its first step.

The popcount is cast to int32 before it is summed: Mosaic has no
reductions over unsigned integers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import interpret_mode

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_W = 512


def _bitset_batch_kernel(planes_ref, out_ref, cnt_ref, *, op: str):
    tile = planes_ref[...]                      # (bq, T, bw) uint32
    combined = tile[:, 0]
    for t in range(1, tile.shape[1]):
        combined = (combined & tile[:, t]) if op == "and" \
            else (combined | tile[:, t])
    out_ref[...] = combined

    @pl.when(pl.program_id(1) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    pc = jax.lax.population_count(combined).astype(jnp.int32)
    cnt_ref[...] += jnp.sum(pc, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("op", "block_q", "block_w",
                                             "interpret"))
def bitset_reduce_batch_pallas(planes, *, op: str = "and",
                               block_q: int = DEFAULT_BLOCK_Q,
                               block_w: int = DEFAULT_BLOCK_W,
                               interpret: bool | None = None):
    """planes (Q, T, W) uint32 -> (combined (Q, W) uint32, counts (Q,)).

    Grid over (query-block, word-block); one dispatch evaluates the
    boolean consumer of a whole query wave.  Q must be a block_q multiple
    and W a block_w multiple (ops.py pads)."""
    q, t, w = planes.shape
    assert w % block_w == 0 and q % block_q == 0
    grid = (q // block_q, w // block_w)
    combined, counts = pl.pallas_call(
        functools.partial(_bitset_batch_kernel, op=op),
        grid=grid,
        in_specs=[pl.BlockSpec((block_q, t, block_w),
                               lambda qi, wi: (qi, 0, wi))],
        out_specs=[pl.BlockSpec((block_q, block_w),
                                lambda qi, wi: (qi, wi)),
                   pl.BlockSpec((block_q, 1), lambda qi, wi: (qi, 0))],
        out_shape=[jax.ShapeDtypeStruct((q, w), jnp.uint32),
                   jax.ShapeDtypeStruct((q, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(planes)
    return combined, counts[:, 0]
