"""Pallas TPU kernel: blocked corpus scoring (two-tower retrieval_cand).

One user embedding against a 1M-row candidate corpus: a tall GEMV.  The
kernel tiles the corpus (C, D) into (block_c, D) VMEM tiles and runs
(block_c, D) x (D, 1) on the MXU per grid step; the query vector is
broadcast to every step.  Arithmetic intensity is ~2 flops/byte — the op
is HBM-bandwidth-bound, so the only thing that matters is streaming the
corpus tiles at full bandwidth, which the sequential grid does.

block_c = 2048 rows x 256 f32 = 2 MiB/tile, double-buffered by Pallas.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..backend import interpret_mode

DEFAULT_BLOCK_C = 2048


def _score_kernel(corpus_ref, query_ref, out_ref):
    out_ref[...] = jax.lax.dot_general(
        corpus_ref[...], query_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def retrieval_score_pallas(corpus, query, *,
                           block_c: int = DEFAULT_BLOCK_C,
                           interpret: bool | None = None):
    """corpus (C, D), query (1, D) -> scores (C, 1)."""
    c, d = corpus.shape
    assert c % block_c == 0
    grid = (c // block_c,)
    out = pl.pallas_call(
        _score_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_c, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_c, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c, 1), jnp.float32),
        interpret=interpret_mode(interpret),
    )(corpus, query)
    return out
