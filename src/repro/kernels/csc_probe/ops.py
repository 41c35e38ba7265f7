"""Public wrapper for the CSC probe kernel."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import DEFAULT_BLOCK_Q, csc_probe_pallas
from .ref import csc_probe_ref  # noqa: F401


def csc_partition_mask(sketch, fps, *, block_q: int = DEFAULT_BLOCK_Q):
    """Batched CSC probe of a baselines.csc.CSCSketch -> (Q, p) bool."""
    fps = jnp.asarray(fps, jnp.uint32)
    q = fps.shape[0]
    block_q = min(block_q, max(8, 1 << (q - 1).bit_length()))
    pad = (-q) % block_q
    if pad:
        fps = jnp.pad(fps, (0, pad))
    out = csc_probe_pallas(fps, jnp.asarray(sketch.bits), m=sketch.m,
                           k=sketch.k, p=sketch.p, j=sketch.j,
                           block_q=block_q)
    return out[:q].astype(bool)
