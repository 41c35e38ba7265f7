"""Public wrapper: pads to the block size (the kernel compiles natively
on a TPU and is interpreted elsewhere, see ``kernels.backend``)."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import DEFAULT_BLOCK_N, token_hash_pallas
from .ref import token_hash_ref  # noqa: F401  (re-export for tests)


def token_fingerprints(tokens_u8, lengths, *, block_n: int = DEFAULT_BLOCK_N):
    """(N, L) uint8 + (N,) lengths -> (N,) uint32 fingerprints."""
    n = tokens_u8.shape[0]
    block_n = min(block_n, max(8, 1 << (n - 1).bit_length()))
    pad = (-n) % block_n
    if pad:
        tokens_u8 = jnp.pad(tokens_u8, ((0, pad), (0, 0)))
        lengths = jnp.pad(lengths, (0, pad))
    out = token_hash_pallas(tokens_u8, lengths, block_n=block_n)
    return out[:n]
