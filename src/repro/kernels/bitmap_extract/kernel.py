"""Pallas TPU kernel: on-device candidate extraction (bitmap -> ids).

Compacts each query's (W,) hit bitmap into its ascending list of set-bit
positions, so only the final (Q, max_hits) int32 tensor crosses to the
host instead of a full (Q, 32*W) bit matrix.

Grid: (row blocks of 8 queries, slot blocks of ``block_s`` output
slots).  Every step works on whole (8, 128)-aligned tiles with no
dynamic scalar reads, no gathers and no unaligned stores.  For each of
its 8 rows it computes, with word index on sublanes and output slot on
lanes:

  * the row's words and their inclusive popcount prefix as (Wp, 1)
    columns — masked lane reductions of the (1, Wp) row over a (Wp, Wp)
    triangle, which also transposes the row without a transpose op;
  * for each slot s, the word holding the (s+1)-th set bit: the number
    of words whose inclusive prefix is <= s (a sublane count);
  * that word's value and the bits before it, picked by a one-hot
    sublane reduction;
  * the bit lane inside the word by a 5-step binary select on
    popcounts of its low bits.

Slots at or past the row's popcount hold -1.  The bitmap arrives as
int32 (the same bits), since Mosaic reduces no unsigned integers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import interpret_mode

ROWS = 8                # queries per grid step (one sublane tile)
DEFAULT_BLOCK_S = 512   # output slots per grid step


def _extract_kernel(bm_ref, out_ref, *, block_s: int):
    wp = bm_ref.shape[1]
    sub = jax.lax.broadcasted_iota(jnp.int32, (wp, wp), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (wp, wp), 1)
    word_ids = jax.lax.broadcasted_iota(jnp.int32, (wp, block_s), 0)
    slot = pl.program_id(1) * block_s \
        + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    for r in range(ROWS):
        row = bm_ref[r:r + 1, :]                                 # (1, Wp)
        pc = jax.lax.population_count(row)
        word_col = jnp.sum(jnp.where(sub == lane, row, 0),
                           axis=1, keepdims=True)                # (Wp, 1)
        incl_col = jnp.sum(jnp.where(lane <= sub, pc, 0),
                           axis=1, keepdims=True)                # (Wp, 1)
        excl_col = incl_col - jax.lax.population_count(word_col)
        total = jnp.sum(pc, axis=1, keepdims=True)               # (1, 1)
        # word of each slot: how many words end at or before it
        word = jnp.sum((incl_col <= slot).astype(jnp.int32),
                       axis=0, keepdims=True)                    # (1, bs)
        hot = word_ids == word                                   # (Wp, bs)
        wv = jnp.sum(jnp.where(hot, word_col, 0), axis=0, keepdims=True)
        rank = slot - jnp.sum(jnp.where(hot, excl_col, 0),
                              axis=0, keepdims=True)             # in-word
        bit = jnp.zeros_like(rank)
        for b in (16, 8, 4, 2, 1):
            low = jax.lax.shift_right_logical(wv, bit) & ((1 << b) - 1)
            cnt = jax.lax.population_count(low)
            up = cnt <= rank
            rank = rank - jnp.where(up, cnt, 0)
            bit = bit + jnp.where(up, b, 0)
        out_ref[r:r + 1, :] = jnp.where(slot < total, word * 32 + bit, -1)


@functools.partial(jax.jit, static_argnames=("n_slots", "block_s",
                                             "interpret"))
def bitmap_extract_pallas(bitmaps, *, n_slots: int,
                          block_s: int = DEFAULT_BLOCK_S,
                          interpret: bool | None = None):
    """bitmaps (Q, Wp) int32, Q a multiple of 8 and Wp of 128 ->
    (Q, n_slots) int32 set-bit positions, -1 past each row's count.
    ``n_slots`` must be a multiple of ``block_s``, and ``block_s`` of 128
    (ops.py pads)."""
    q, wp = bitmaps.shape
    assert q % ROWS == 0 and wp % 128 == 0
    assert block_s % 128 == 0 and n_slots % block_s == 0
    return pl.pallas_call(
        functools.partial(_extract_kernel, block_s=block_s),
        grid=(q // ROWS, n_slots // block_s),
        in_specs=[pl.BlockSpec((ROWS, wp), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((ROWS, block_s), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, n_slots), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret_mode(interpret),
    )(bitmaps)
