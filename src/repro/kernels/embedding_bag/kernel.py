"""Pallas TPU kernel: EmbeddingBag (sum) — the recsys lookup hot path.

JAX has no native EmbeddingBag; the framework's jnp path is
take+segment_sum (models/recsys.py).  This kernel is the TPU-native
version for the fixed-bag layout (B, BAG) used by every assigned recsys
arch: the bag indices are *scalar-prefetched* so the BlockSpec index_map
can steer the table-row DMA per grid step — the canonical Pallas TPU
embedding-gather pattern.  The table itself never leaves HBM; each grid
step DMAs exactly one (row_block, D) tile into VMEM and accumulates into
the output block.

Grid: (B, BAG).  Output block (1, D) at row b is revisited across the
BAG axis (index_map j -> same out block), accumulating in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import interpret_mode


def _ebag_kernel(idx_ref, table_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += table_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag_pallas(table, idx, *, interpret: bool | None = None):
    """table (V, D) f32; idx (B, BAG) int32 -> (B, D) f32 bag sums."""
    b, bag = idx.shape
    v, d = table.shape
    grid = (b, bag)
    out = pl.pallas_call(
        _ebag_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # one table row per step, row chosen by the prefetched idx
                pl.BlockSpec((1, d), lambda i, j, idx_p: (idx_p[i, j], 0)),
            ],
            out_specs=pl.BlockSpec((1, d), lambda i, j, idx_p: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        interpret=interpret_mode(interpret),
    )(idx, table)
    return out
