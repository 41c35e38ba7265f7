"""Public wrapper: pad to the kernel's blocks, fold, unpad."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_W,
                     bitset_reduce_batch_pallas)
from .ref import bitset_reduce_batch_ref, bitset_reduce_ref  # noqa: F401


def bitset_reduce(planes, *, op: str = "and", block_w: int = DEFAULT_BLOCK_W):
    """(T, W) uint32 posting planes -> (combined plane, set-bit count).
    AND: candidate batches containing every query token; OR: any token."""
    combined, counts = bitset_reduce_batch(planes[None], op=op,
                                           block_w=block_w)
    return combined[0], counts[0]


def bitset_reduce_batch(planes, *, op: str = "and",
                        block_w: int = DEFAULT_BLOCK_W):
    """(Q, T, W) uint32 posting planes -> ((Q, W) combined, (Q,) counts).
    One kernel dispatch reduces every query's token planes."""
    q, t, w = planes.shape
    block_w = min(block_w, 128 * -(-w // 128))    # lane-aligned
    pad = (-w) % block_w
    if pad:
        fill = jnp.uint32(0xFFFFFFFF if op == "and" else 0)
        planes = jnp.pad(planes, ((0, 0), (0, 0), (0, pad)),
                         constant_values=fill)
    block_q = min(DEFAULT_BLOCK_Q, max(8, 1 << (q - 1).bit_length()))
    pad_q = (-q) % block_q
    if pad_q:
        planes = jnp.pad(planes, ((0, pad_q), (0, 0), (0, 0)))
    combined, counts = bitset_reduce_batch_pallas(
        planes, op=op, block_q=block_q, block_w=block_w)
    if pad_q:
        combined, counts = combined[:q], counts[:q]
    if pad:
        # padded words were all-ones under AND; correct both outputs
        combined = combined[:, :w]
        counts = counts - (pad * 32 if op == "and" else 0)
    return combined, counts

