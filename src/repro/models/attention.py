"""Attention for the LM family: GQA, RoPE, sliding-window/global alternation,
attn-logit soft-capping, blockwise (flash-style) training attention and
KV-cache decode attention.

Memory design: training/prefill attention is computed *blockwise* (scan over
KV chunks with a running (max, sum) online softmax) so the (S x S) score
matrix never materializes — at 32k context the naive scores would be
S^2 * H * B * 2B >> HBM.  This is the XLA-level equivalent of
FlashAttention; the Pallas kernel in kernels/flash_decode further fuses the
decode path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import apply_rope, rope_table, softcap

NEG_INF = -2.0e38


def repeat_kv(x, n_rep: int):
    """(B, S, Hkv, D) -> (B, S, Hkv*n_rep, D) by head repetition (GQA)."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)) \
              .reshape(b, s, h * n_rep, d)


def _chunk_mask(q_pos, k_pos, *, causal: bool, window: int | None):
    """(Sq, Sk) bool mask: True = attend."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


def blockwise_attention(q, k, v, *, causal: bool = True,
                        window: int | None = None,
                        attn_softcap: float | None = None,
                        q_chunk: int = 512, kv_chunk: int = 1024,
                        q_offset: int = 0):
    """Flash-style attention, O(S*chunk) memory.

    q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D) with Hq % Hkv == 0.
    ``q_offset``: absolute position of q[0] (for chunked prefill).
    Returns (B, Sq, Hq, D).
    """
    b, sq0, hq, d = q.shape
    sk0, hkv = k.shape[1], k.shape[2]
    n_rep = hq // hkv
    scale = d ** -0.5
    q_chunk = min(q_chunk, sq0)
    kv_chunk = min(kv_chunk, sk0)
    # pad to chunk multiples; padded kv positions are masked below via
    # k_pos >= sk0, padded q rows are sliced away at the end.
    sq = -(-sq0 // q_chunk) * q_chunk
    sk = -(-sk0 // kv_chunk) * kv_chunk
    if sq != sq0:
        q = jnp.pad(q, ((0, 0), (0, sq - sq0), (0, 0), (0, 0)))
    if sk != sk0:
        k = jnp.pad(k, ((0, 0), (0, sk - sk0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk - sk0), (0, 0), (0, 0)))

    # (B, Hkv, G, S, D) layout: group dim keeps GQA matmuls batched.
    qh = q.reshape(b, sq, hkv, n_rep, d).transpose(0, 2, 3, 1, 4)
    kh = k.transpose(0, 2, 1, 3)  # (B, Hkv, Sk, D)
    vh = v.transpose(0, 2, 1, 3)

    nq, nk = sq // q_chunk, sk // kv_chunk
    qh = qh.reshape(b, hkv, n_rep, nq, q_chunk, d)

    def q_block(qi, q_blk):
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, ki):
            acc, m_run, l_run = carry
            k_blk = jax.lax.dynamic_slice_in_dim(kh, ki * kv_chunk,
                                                 kv_chunk, axis=2)
            v_blk = jax.lax.dynamic_slice_in_dim(vh, ki * kv_chunk,
                                                 kv_chunk, axis=2)
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            # scores: (B, Hkv, G, Qc, Kc)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            s = softcap(s, attn_softcap)
            mask = _chunk_mask(q_pos, k_pos, causal=causal, window=window)
            mask &= (k_pos < sk0)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((b, hkv, n_rep, q_chunk, d), jnp.float32)
        m0 = jnp.full((b, hkv, n_rep, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, n_rep, q_chunk), jnp.float32)
        (acc, _, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                      jnp.arange(nk))
        return acc / jnp.maximum(l[..., None], 1e-30)

    out = jax.lax.map(lambda qi: q_block(qi, qh[:, :, :, qi]),
                      jnp.arange(nq)) if nq > 1 else \
        q_block(jnp.int32(0), qh[:, :, :, 0])[None]
    # out: (nq, B, Hkv, G, Qc, D) -> (B, Sq, Hq, D)
    out = jnp.moveaxis(out, 0, 3).reshape(b, hkv, n_rep, sq, d)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d)
    return out[:, :sq0].astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     window: int | None = None,
                     attn_softcap: float | None = None):
    """One-token decode: q (B, 1, Hq, D) vs cache (B, S, Hkv, D).

    ``cache_len``: number of valid cache positions (scalar int32);
    positions >= cache_len are masked.  Window masking restricts to the
    trailing ``window`` positions (sliding-window layers).
    """
    b, _, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    n_rep = hq // hkv
    qh = q.reshape(b, hkv, n_rep, d)
    scores = jnp.einsum("bhgd,bshd->bhgs", qh, k_cache,
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    scores = softcap(scores, attn_softcap)
    pos = jnp.arange(s)
    valid = pos < cache_len
    if window is not None:
        valid &= pos >= cache_len - window
    scores = jnp.where(valid[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def seq_parallel_attention(q, k, v, *, batch_axes, model_axis,
                           causal=True, window=None, attn_softcap=None,
                           q_chunk=512, kv_chunk=1024):
    """Sequence-parallel attention core (It. 7, EXPERIMENTS.md §Perf).

    For archs whose head counts don't divide the TP axis (arctic: 56 q /
    8 kv vs model=16) the attention core would otherwise run replicated
    on every model shard (2.6x HLO flops at train).  Here the QUERY
    sequence shards over 'model' (each shard computes its S/16 rows
    against the full K/V — a 16 MB/layer bf16 gather at S=4096), so the
    core compute splits 16-ways with the causal offset supplied per
    shard."""
    from jax.sharding import PartitionSpec as P
    sq = q.shape[1]

    def inner(q_loc, k_loc, v_loc):
        idx = jax.lax.axis_index(model_axis)
        return blockwise_attention(
            q_loc, k_loc, v_loc, causal=causal, window=window,
            attn_softcap=attn_softcap, q_chunk=q_chunk, kv_chunk=kv_chunk,
            q_offset=idx * q_loc.shape[1])

    return jax.shard_map(
        inner,
        in_specs=(P(batch_axes, model_axis, None, None),
                  P(batch_axes, None, None, None),
                  P(batch_axes, None, None, None)),
        out_specs=P(batch_axes, model_axis, None, None),
        check_vma=False)(q, k, v)


def attention_block(x, w, *, n_heads: int, n_kv_heads: int, d_head: int,
                    rope_theta: float, causal: bool = True,
                    window: int | None = None,
                    attn_softcap: float | None = None,
                    positions=None,
                    kv_cache=None, cache_len=None,
                    q_chunk: int = 512, kv_chunk: int = 1024,
                    seq_parallel=None):
    """Full attention sub-layer: qkv proj + rope + attention + out proj.

    w: dict(wq (D, Hq*Dh), wk (D, Hkv*Dh), wv, wo (Hq*Dh, D)).
    Train/prefill mode (kv_cache None): returns (out, (k, v)) — the full
    per-layer K/V for cache construction.
    Decode mode: x is (B, 1, D), kv_cache = (k_cache, v_cache) of shape
    (B, S, Hkv, D); the new token's K/V is written at ``cache_len`` and the
    updated caches are returned: (out, (k_cache', v_cache')).
    """
    b, s, _ = x.shape
    q = (x @ w["wq"]).reshape(b, s, n_heads, d_head)
    k = (x @ w["wk"]).reshape(b, s, n_kv_heads, d_head)
    v = (x @ w["wv"]).reshape(b, s, n_kv_heads, d_head)
    if positions is None:
        positions = (jnp.arange(s)[None] if kv_cache is None
                     else jnp.full((1, 1), cache_len, jnp.int32))
    cos, sin = rope_table(positions, d_head, rope_theta, dtype=jnp.float32)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if kv_cache is None:
        if seq_parallel is not None:
            bd, ma = seq_parallel
            out = seq_parallel_attention(
                q, k, v, batch_axes=bd, model_axis=ma, causal=causal,
                window=window, attn_softcap=attn_softcap,
                q_chunk=q_chunk, kv_chunk=kv_chunk)
        else:
            out = blockwise_attention(q, k, v, causal=causal,
                                      window=window,
                                      attn_softcap=attn_softcap,
                                      q_chunk=q_chunk, kv_chunk=kv_chunk)
        new_kv = (k, v)
    else:
        k_cache, v_cache = kv_cache
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, cache_len,
                                                      axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, cache_len,
                                                      axis=1)
        out = decode_attention(q, k_cache, v_cache, cache_len + 1,
                               window=window, attn_softcap=attn_softcap)
        new_kv = (k_cache, v_cache)
    out = out.reshape(b, s, n_heads * d_head) @ w["wo"]
    return out, new_kv
