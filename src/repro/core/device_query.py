"""Beyond-paper: fully device-side batched query evaluation.

The paper's Alg. 3 is a sequential host loop (probe token -> decode list
-> boolean consumer).  On TPU the same semantics evaluate as dense
bitmap algebra in ONE jit:  Q queries x T tokens probe the sketch
(MPHF + signatures + CSF) -> each token resolves to its posting-plane
row -> AND/OR across the token axis -> per-query candidate bitmaps +
popcounts.  The bitset_ops Pallas kernel folds the planes; everything
stays in device memory, so a query wave over many segments is
collective-free until the final candidate gather.

Requires the immutable sketch to be built with bitmap planes
(build_immutable(..., plane_budget_bytes=...)), which the paper's layout
supports for segments whose n_lists x n_postings/8 fits the budget.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp


def batched_match_bitmaps(sketch, fps, arrs=None):
    """fps (Q, T) int32/uint32 -> (Q, T, W) uint32 posting bitmaps
    (absent tokens give zero rows)."""
    q, t = fps.shape
    rows = sketch.match_bitmap_jnp(jnp.asarray(fps).reshape(-1), arrs)
    return rows.reshape(q, t, -1)


def batched_query(sketch, fps, *, op: str = "and", arrs=None):
    """Alg. 3 for a (Q, T) token batch in one jit.

    Returns (bitmaps (Q, W) uint32, counts (Q,) int32).  ``op='and'``:
    batches containing every token of the query; ``'or'``: any token."""
    from ..kernels.bitset_ops.ops import bitset_reduce_batch
    planes = batched_match_bitmaps(sketch, fps, arrs)       # (Q, T, W)
    return bitset_reduce_batch(planes, op=op)


def bitmap_to_postings(bitmap_row: np.ndarray, n_postings: int) -> np.ndarray:
    """Host-side expansion of one (W,) uint32 bitmap into posting ids."""
    bits = np.unpackbits(
        np.asarray(bitmap_row, dtype=np.uint32).view(np.uint8),
        bitorder="little")
    return np.nonzero(bits[:n_postings])[0].astype(np.int64)
