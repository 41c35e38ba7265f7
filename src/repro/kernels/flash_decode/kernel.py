"""Pallas TPU kernel: flash-decode — one-token GQA attention against a
long KV cache (the decode_32k / long_500k serving hot path).

FlashDecoding-style split-KV schedule adapted to TPU: the cache streams
through VMEM in (block_s, Hkv, D) tiles while a running online-softmax
state (m, l, acc) lives in revisited output blocks; a single query tile
(Hq, D) stays resident.  The sequence axis is the innermost grid dim so
the accumulation order is deterministic; `cache_len` arrives via scalar
prefetch and masks the tail block.

This is the memory-roofline op of LM serving (2 bytes/flop): the kernel's
only job is streaming KV tiles at full HBM bandwidth — block_s = 512
rows x Hkv x D keeps tiles MXU-aligned and double-buffered.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import interpret_mode

DEFAULT_BLOCK_S = 512
NEG_INF = -2.0e38


def _flash_decode_kernel(len_ref, q_ref, k_ref, v_ref,
                         acc_ref, m_ref, l_ref, *, block_s: int,
                         n_rep: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                  # (Hq, D)
    k = k_ref[0]                                  # (bs, Hkv, D)
    v = v_ref[0]
    bs, hkv, d = k.shape
    qh = q.reshape(hkv, n_rep, d)
    # scores (Hkv, G, bs)
    s = jnp.einsum("hgd,shd->hgs", qh, k,
                   preferred_element_type=jnp.float32) * (d ** -0.5)
    pos = j * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
    s = jnp.where(pos < len_ref[0], s, NEG_INF)

    m_prev = m_ref[0].reshape(hkv, n_rep, 1)      # (Hq, 1)-> (Hkv, G, 1)
    l_prev = l_ref[0].reshape(hkv, n_rep, 1)
    acc_prev = acc_ref[0].reshape(hkv, n_rep, d)

    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("hgs,shd->hgd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    acc_new = acc_prev * corr + pv

    acc_ref[...] = acc_new.reshape(1, hkv * n_rep, d)
    m_ref[...] = m_new.reshape(1, hkv * n_rep, 1)
    l_ref[...] = l_new.reshape(1, hkv * n_rep, 1)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode_pallas(q, k_cache, v_cache, cache_len, *,
                        block_s: int = DEFAULT_BLOCK_S,
                        interpret: bool | None = None):
    """q (B, Hq, D); k_cache/v_cache (B, S, Hkv, D); cache_len () int32.
    Returns (B, Hq, D) attention output.  S must be a block_s multiple
    (ops.py pads with masked positions)."""
    b, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    assert s % block_s == 0
    n_rep = hq // hkv
    grid = (b, s // block_s)
    acc, m, l = pl.pallas_call(
        functools.partial(_flash_decode_kernel, block_s=block_s,
                          n_rep=n_rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, hq, d), lambda i, j, L: (i, 0, 0)),
                pl.BlockSpec((1, block_s, hkv, d),
                             lambda i, j, L: (i, j, 0, 0)),
                pl.BlockSpec((1, block_s, hkv, d),
                             lambda i, j, L: (i, j, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, hq, d), lambda i, j, L: (i, 0, 0)),
                pl.BlockSpec((1, hq, 1), lambda i, j, L: (i, 0, 0)),
                pl.BlockSpec((1, hq, 1), lambda i, j, L: (i, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, 1), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(jnp.atleast_1d(cache_len).astype(jnp.int32), q, k_cache, v_cache)
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
