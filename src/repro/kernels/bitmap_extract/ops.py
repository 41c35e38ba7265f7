"""Public wrapper: pad to the kernel's tiles, extract, cut to max_hits."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import DEFAULT_BLOCK_S, ROWS, bitmap_extract_pallas
from .ref import bitmap_extract_ref  # noqa: F401


def bitmap_extract(bitmaps, *, max_hits: int):
    """(Q, W) uint32 hit bitmaps -> ((Q, max_hits) int32, (Q,) int32).

    Row i holds its bitmap's set-bit positions (ascending), -1-padded;
    hits past ``max_hits`` are dropped (callers size ``max_hits`` from the
    wave's popcounts, so real waves never truncate)."""
    bitmaps = jax.lax.bitcast_convert_type(
        jnp.asarray(bitmaps, jnp.uint32), jnp.int32)
    q, w = bitmaps.shape
    counts = jnp.sum(jax.lax.population_count(bitmaps), axis=1,
                     dtype=jnp.int32)
    block_s = min(DEFAULT_BLOCK_S, 128 * -(-max_hits // 128))
    n_slots = block_s * -(-max_hits // block_s)
    padded = jnp.pad(bitmaps, ((0, (-q) % ROWS), (0, (-w) % 128)))
    ids = bitmap_extract_pallas(padded, n_slots=n_slots, block_s=block_s)
    return ids[:q, :max_hits], counts
