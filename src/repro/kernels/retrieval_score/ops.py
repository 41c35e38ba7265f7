"""Public wrapper: pad corpus rows, return (scores, top-k)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import DEFAULT_BLOCK_C, retrieval_score_pallas
from .ref import retrieval_score_ref  # noqa: F401


def retrieval_scores(corpus, query, *, block_c: int = DEFAULT_BLOCK_C):
    """corpus (C, D), query (D,) -> (C,) scores."""
    c, d = corpus.shape
    block_c = min(block_c, max(8, 1 << (c - 1).bit_length()))
    pad = (-c) % block_c
    if pad:
        corpus = jnp.pad(corpus, ((0, pad), (0, 0)))
    out = retrieval_score_pallas(corpus, query[None].astype(corpus.dtype),
                                 block_c=block_c)
    return out[:c, 0]


def retrieval_topk(corpus, query, k: int = 100):
    scores = retrieval_scores(corpus, query)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx
