"""Public wrapper: pads the cache to the block size (padded positions are
masked via cache_len)."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import DEFAULT_BLOCK_S, flash_decode_pallas
from .ref import flash_decode_ref  # noqa: F401


def flash_decode(q, k_cache, v_cache, cache_len, *,
                 block_s: int = DEFAULT_BLOCK_S):
    """q (B, Hq, D); caches (B, S, Hkv, D); cache_len scalar int32."""
    s = k_cache.shape[1]
    block_s = min(block_s, max(8, 1 << (s - 1).bit_length()))
    pad = (-s) % block_s
    if pad:
        cfg = ((0, 0), (0, pad), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, cfg)
        v_cache = jnp.pad(v_cache, cfg)
    return flash_decode_pallas(q, k_cache, v_cache, cache_len,
                               block_s=block_s)
