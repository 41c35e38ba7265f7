"""Configurable LM-family transformer covering the five assigned archs:

  gemma2-9b   : GQA, local(4096)+global alternating attention, attn-logit
                softcap 50, final-logit softcap 30, sandwich norms, tied
                embeddings, d_head 256.
  olmo-1b     : MHA (kv=16), non-parametric LayerNorm, tied embeddings.
  llama3-8b   : GQA kv=8, SwiGLU, RMSNorm, 128k vocab, rope 500k.
  phi3.5-moe  : GQA kv=8, 16-expert top-2 MoE FFN.
  arctic-480b : GQA kv=8, 128-expert top-2 MoE + parallel dense-residual FFN.

Pure JAX (no flax): params are nested dicts with a stacked leading layer dim
so the whole stack runs under one lax.scan (compile-time O(1) in depth) with
jax.checkpoint remat.  Training uses microbatched gradient accumulation and
sequence-chunked cross-entropy so 256k-vocab logits never materialize.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp

from .attention import attention_block
from .layers import (cross_entropy_loss, dense_init, embed_init,
                     layer_norm_nonparam, rms_norm, softcap)
from .moe import moe_ffn


@dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int | None = None          # default d_model // n_heads
    rope_theta: float = 10000.0
    norm: str = "rms"                  # "rms" | "nonparam"
    post_norm: bool = False            # gemma2 sandwich norms
    attn_softcap: float | None = None
    final_softcap: float | None = None
    sliding_window: int | None = None  # window for local layers
    local_global_period: int = 0       # 0: all global; 2: alternate (gemma2)
    tie_embeddings: bool = True
    embed_scale: bool = False          # gemma: x *= sqrt(d_model)
    # MoE
    n_experts: int = 0
    top_k: int = 2
    moe_dff: int | None = None
    dense_residual: bool = False       # arctic: dense FFN in parallel to MoE
    dense_residual_dff: int | None = None
    capacity_factor: float = 1.25
    # numerics / scheduling
    dtype: str = "bfloat16"
    q_chunk: int = 512
    kv_chunk: int = 1024
    ce_chunk: int = 512
    aux_loss_weight: float = 0.01
    # scan_layers=False unrolls the layer stack (python loop).  Used by the
    # dry-run analysis pass: XLA's cost model counts while-loop bodies ONCE
    # regardless of trip count, so roofline FLOPs are extracted from small
    # unrolled variants and fit linearly in n_layers (see launch/dryrun.py).
    scan_layers: bool = True
    # mesh-aware MoE dispatch (set by launch/steps.build_bundle): when
    # moe_expert_axis is set, _ffn uses the shard_map expert-parallel
    # dispatch (moe.moe_ffn_sharded) instead of the single-device global
    # sort dispatch.
    moe_batch_axes: tuple | None = None
    moe_expert_axis: str | None = None
    moe_fsdp_axis: str | None = None
    moe_expert_parallel: int | None = None   # mesh size of the expert axis
    # pin the residual stream to (batch over data, d_model over model) —
    # 2D activation sharding.  Without this GSPMD dropped the batch
    # sharding of the remat carry stack on gemma2/arctic (replicating the
    # microbatch per chip, 9+ GiB); sharding d_model over 'model' between
    # blocks additionally divides the remat stacks by the TP degree (XLA
    # inserts the all-gather before QKV and reduce-scatter after wo — same
    # wire bytes as the Megatron all-reduce it replaces).
    act_batch_axes: tuple | None = None
    act_model_axis: str | None = None
    # sequence-parallel attention core (It. 7): set for archs whose head
    # counts don't divide the TP axis, where the core would otherwise run
    # replicated on every model shard.
    attn_seq_parallel: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def layer_is_local(self, i: int) -> bool:
        return (self.local_global_period > 0
                and i % self.local_global_period == 0
                and self.sliding_window is not None)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def param_count(self) -> int:
        """Analytic N (all params)."""
        d, dh = self.d_model, self.head_dim
        attn = d * (self.n_heads + 2 * self.n_kv_heads) * dh \
            + self.n_heads * dh * d
        if self.is_moe:
            f = self.moe_dff or self.d_ff
            ffn = self.n_experts * 3 * d * f + d * self.n_experts
            if self.dense_residual:
                ffn += 3 * d * (self.dense_residual_dff or self.d_ff)
        else:
            ffn = 3 * d * self.d_ff
        per_layer = attn + ffn
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb

    def active_param_count(self) -> int:
        """N_active (MoE: only routed experts) for MODEL_FLOPS = 6*N_a*D."""
        if not self.is_moe:
            return self.param_count()
        d = self.d_model
        f = self.moe_dff or self.d_ff
        attn = d * (self.n_heads + 2 * self.n_kv_heads) * self.head_dim \
            + self.n_heads * self.head_dim * d
        ffn = self.top_k * 3 * d * f + d * self.n_experts
        if self.dense_residual:
            ffn += 3 * d * (self.dense_residual_dff or self.d_ff)
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * (attn + ffn) + emb


# --------------------------------------------------------------------- init
def init_params(cfg: LMConfig, rng):
    """Stacked-layer param pytree; usable under jax.eval_shape."""
    dt = cfg.compute_dtype
    d, dh, l = cfg.d_model, cfg.head_dim, cfg.n_layers
    ks = jax.random.split(rng, 16)

    def stack(key, shape, fan_in):
        return (jax.random.normal(key, (l, *shape), jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    layer = {
        "wq": stack(ks[0], (d, cfg.n_heads * dh), d),
        "wk": stack(ks[1], (d, cfg.n_kv_heads * dh), d),
        "wv": stack(ks[2], (d, cfg.n_kv_heads * dh), d),
        "wo": stack(ks[3], (cfg.n_heads * dh, d), cfg.n_heads * dh),
        "ln_attn": jnp.zeros((l, d), dt),
        "ln_ffn": jnp.zeros((l, d), dt),
    }
    if cfg.post_norm:
        layer["ln_attn_post"] = jnp.zeros((l, d), dt)
        layer["ln_ffn_post"] = jnp.zeros((l, d), dt)
    if cfg.is_moe:
        f = cfg.moe_dff or cfg.d_ff
        layer["moe"] = {
            "router": stack(ks[4], (d, cfg.n_experts), d),
            "w_gate": stack(ks[5], (cfg.n_experts, d, f), d),
            "w_up": stack(ks[6], (cfg.n_experts, d, f), d),
            "w_down": stack(ks[7], (cfg.n_experts, f, d), f),
        }
        if cfg.dense_residual:
            fd = cfg.dense_residual_dff or cfg.d_ff
            layer["dense"] = {
                "w_gate": stack(ks[8], (d, fd), d),
                "w_up": stack(ks[9], (d, fd), d),
                "w_down": stack(ks[10], (fd, d), fd),
            }
    else:
        layer["mlp"] = {
            "w_gate": stack(ks[5], (d, cfg.d_ff), d),
            "w_up": stack(ks[6], (d, cfg.d_ff), d),
            "w_down": stack(ks[7], (cfg.d_ff, d), cfg.d_ff),
        }
    params = {
        "embed": embed_init(ks[11], cfg.vocab, d, dt),
        "layers": layer,
        "ln_final": jnp.zeros((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(ks[12], d, cfg.vocab, dt)
    return params


# ------------------------------------------------------------------ forward
def _norm(cfg: LMConfig, x, w):
    if cfg.act_model_axis is not None and x.ndim == 3 \
            and x.shape[-1] == cfg.d_model:
        return _norm_sharded(cfg, x, w)
    if cfg.norm == "nonparam":
        return layer_norm_nonparam(x)
    return rms_norm(x, w)


def _norm_sharded(cfg: LMConfig, x, w):
    """Norm over the model-sharded d_model axis via shard_map.

    Why: with 2D activation sharding GSPMD preferred to ALL-GATHER the
    f32 pre-norm tensor and normalize replicated — 2x wire (f32) and 10+
    gathers/layer on gemma2 train (EXPERIMENTS.md §Perf iteration 2/3).
    Computing the reduction per shard (one scalar-row psum) keeps every
    cross-shard tensor bf16 and moves only (B, S, 1) floats for the
    statistics."""
    from jax.sharding import PartitionSpec as P
    bd, ma = cfg.act_batch_axes, cfg.act_model_axis
    spec = P(bd, None, ma)
    d = cfg.d_model
    eps = 1e-6 if cfg.norm == "rms" else 1e-5
    nonparam = cfg.norm == "nonparam"

    def inner(xs, ws):
        x32 = xs.astype(jnp.float32)
        if nonparam:
            s1 = jax.lax.psum(jnp.sum(x32, -1, keepdims=True), ma)
            mu = s1 / d
            s2 = jax.lax.psum(jnp.sum(jnp.square(x32 - mu), -1,
                                      keepdims=True), ma)
            nrm = (x32 - mu) * jax.lax.rsqrt(s2 / d + eps)
        else:
            ssq = jax.lax.psum(jnp.sum(jnp.square(x32), -1,
                                       keepdims=True), ma)
            nrm = x32 * jax.lax.rsqrt(ssq / d + eps)
            nrm = nrm * (1.0 + ws.astype(jnp.float32))
        return nrm.astype(xs.dtype)

    if w is None or nonparam:
        w = jnp.zeros((d,), x.dtype)
    return jax.shard_map(inner, in_specs=(spec, P(ma)), out_specs=spec,
                         check_vma=False)(x, w)


def _constrain_act(cfg: LMConfig, x):
    if cfg.act_batch_axes:
        from jax.sharding import PartitionSpec as P
        spec = P(cfg.act_batch_axes,
                 *([None] * (x.ndim - 2)), cfg.act_model_axis)
        return jax.lax.with_sharding_constraint(x, spec)
    return x


def _ffn(cfg: LMConfig, x, lw):
    b, s, d = x.shape
    if cfg.is_moe:
        if cfg.moe_expert_axis is not None:
            from .moe import moe_ffn_sharded
            y, aux = moe_ffn_sharded(
                x.reshape(b * s, d), lw["moe"],
                n_experts=cfg.n_experts, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor,
                batch_axes=cfg.moe_batch_axes or ("data",),
                expert_axis=cfg.moe_expert_axis,
                fsdp_axis=cfg.moe_fsdp_axis,
                expert_parallel=cfg.moe_expert_parallel)
        else:
            y, aux = moe_ffn(x.reshape(b * s, d), lw["moe"],
                             n_experts=cfg.n_experts, top_k=cfg.top_k,
                             capacity_factor=cfg.capacity_factor)
        y = y.reshape(b, s, d)
        if cfg.dense_residual:
            dw = lw["dense"]
            y = y + (jax.nn.silu(x @ dw["w_gate"]) * (x @ dw["w_up"])) \
                @ dw["w_down"]
        return y, aux
    mw = lw["mlp"]
    return (jax.nn.silu(x @ mw["w_gate"]) * (x @ mw["w_up"])) \
        @ mw["w_down"], jnp.float32(0.0)


def _layer(cfg: LMConfig, x, lw, is_local, *, positions=None,
           kv_cache=None, cache_len=None):
    """One transformer block.  is_local: scalar bool (traced) selecting the
    sliding-window mask.  Returns (x', new_kv, aux)."""
    x = _constrain_act(cfg, x)

    def _boundary(h):
        # Pin the bf16 post-norm value so XLA cannot hoist the f32->bf16
        # convert past the model-axis all-gather: without this the
        # activation gathers move f32 (2x wire, measured on gemma2
        # train_4k — EXPERIMENTS.md §Perf iteration 2).
        return jax.lax.optimization_barrier(h) if cfg.act_batch_axes \
            else h

    window = cfg.sliding_window
    if cfg.local_global_period > 0 and window is not None:
        # one scan body for local+global alternation: the window is a
        # *traced* scalar — local layers use cfg.sliding_window, global
        # layers an effectively-infinite window.  Single attention call,
        # honest FLOPs.
        window = jnp.where(is_local, jnp.int32(window), jnp.int32(1 << 30))
    h = _boundary(_norm(cfg, x, lw["ln_attn"]))
    seq_par = None
    if cfg.attn_seq_parallel and kv_cache is None \
            and cfg.act_batch_axes and cfg.act_model_axis:
        seq_par = (cfg.act_batch_axes, cfg.act_model_axis)
    a, new_kv = attention_block(
        h, lw, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        d_head=cfg.head_dim, rope_theta=cfg.rope_theta,
        window=window, attn_softcap=cfg.attn_softcap,
        positions=positions, kv_cache=kv_cache, cache_len=cache_len,
        q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
        seq_parallel=seq_par)
    if cfg.post_norm:
        a = _norm(cfg, a, lw["ln_attn_post"])
    x = x + a
    h2 = _boundary(_norm(cfg, x, lw["ln_ffn"]))
    y, aux = _ffn(cfg, h2, lw)
    if cfg.post_norm:
        y = _norm(cfg, y, lw["ln_ffn_post"])
    return x + y, new_kv, aux


def forward(cfg: LMConfig, params, tokens, *, positions=None,
            return_kv: bool = False, remat: bool = True):
    """tokens (B, S) -> final hidden (B, S, D), aux loss, and (optionally)
    stacked (L, ...) K/V for cache construction (prefill)."""
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = (x.astype(jnp.float32) * (cfg.d_model ** 0.5)).astype(x.dtype)
    x = _constrain_act(cfg, x)
    is_local = jnp.asarray(
        [cfg.layer_is_local(i) for i in range(cfg.n_layers)])

    def body(carry, per_layer):
        x, aux = carry
        lw, loc = per_layer
        x, kv, a = _layer(cfg, x, lw, loc, positions=positions)
        return (_constrain_act(cfg, x), aux + a), (kv if return_kv else None)

    body_fn = jax.checkpoint(body) if remat else body
    if cfg.scan_layers:
        (x, aux), kvs = jax.lax.scan(body_fn, (x, jnp.float32(0.0)),
                                     (params["layers"], is_local))
    else:  # unrolled: analysis mode (honest HLO cost counting)
        carry, kv_list = (x, jnp.float32(0.0)), []
        for i in range(cfg.n_layers):
            lw = jax.tree.map(lambda a: a[i], params["layers"])
            carry, kv = body_fn(carry, (lw, is_local[i]))
            kv_list.append(kv)
        (x, aux) = carry
        kvs = (jax.tree.map(lambda *xs: jnp.stack(xs),
                            *kv_list) if return_kv else None)
    x = _norm(cfg, x, params["ln_final"])
    return x, aux, kvs


def _unembed(cfg: LMConfig, params, h):
    w = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = h @ w
    return softcap(logits, cfg.final_softcap)


def chunked_ce_loss(cfg: LMConfig, params, h, labels, mask):
    """Sequence-chunked CE: logits only ever exist for ce_chunk positions."""
    b, s, d = h.shape
    c = min(cfg.ce_chunk, s)
    n = s // c

    def step(carry, i):
        tot, cnt = carry
        hs = jax.lax.dynamic_slice_in_dim(h, i * c, c, axis=1)
        ls = jax.lax.dynamic_slice_in_dim(labels, i * c, c, axis=1)
        ms = jax.lax.dynamic_slice_in_dim(mask, i * c, c, axis=1)
        logits = _unembed(cfg, params, hs).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ls[..., None], axis=-1)[..., 0]
        m = ms.astype(jnp.float32)
        return (tot + jnp.sum((logz - gold) * m), cnt + jnp.sum(m)), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.float32(0.), jnp.float32(0.)),
                                 jnp.arange(n))
    return tot / jnp.maximum(cnt, 1.0)


def lm_loss(cfg: LMConfig, params, batch):
    """batch: dict(tokens (B, S) int32, labels (B, S) int32,
    mask (B, S) — labels already shifted)."""
    h, aux, _ = forward(cfg, params, batch["tokens"])
    ce = chunked_ce_loss(cfg, params, h, batch["labels"], batch["mask"])
    return ce + cfg.aux_loss_weight * aux / cfg.n_layers, ce


# ------------------------------------------------------------------ serving
def init_cache(cfg: LMConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or cfg.compute_dtype
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill(cfg: LMConfig, params, tokens):
    """tokens (B, S) -> (cache filled to S, last-position logits)."""
    h, _, kvs = forward(cfg, params, tokens, return_kv=True)
    cache = {"k": kvs[0], "v": kvs[1]}  # (L, B, S, Hkv, Dh)
    logits = _unembed(cfg, params, h[:, -1:, :])
    return cache, logits[:, 0]


def decode_step(cfg: LMConfig, params, cache, tokens, cache_len):
    """One greedy decode step.  tokens (B,) int32; cache dict of
    (L, B, S, Hkv, Dh); cache_len scalar int32 = #valid positions.
    Returns (new_cache, next_tokens (B,), logits (B, V))."""
    x = params["embed"][tokens[:, None]]
    if cfg.embed_scale:
        x = (x.astype(jnp.float32) * (cfg.d_model ** 0.5)).astype(x.dtype)
    is_local = jnp.asarray(
        [cfg.layer_is_local(i) for i in range(cfg.n_layers)])

    def body(x, per_layer):
        lw, loc, kc, vc = per_layer
        x, (kc, vc), _ = _layer(cfg, x, lw, loc, kv_cache=(kc, vc),
                                cache_len=cache_len)
        return x, (kc, vc)

    if cfg.scan_layers:
        x, (knew, vnew) = jax.lax.scan(
            body, x, (params["layers"], is_local, cache["k"], cache["v"]))
    else:
        ks_, vs_ = [], []
        for i in range(cfg.n_layers):
            lw = jax.tree.map(lambda a: a[i], params["layers"])
            x, (kc, vc) = body(x, (lw, is_local[i], cache["k"][i],
                                   cache["v"][i]))
            ks_.append(kc)
            vs_.append(vc)
        knew, vnew = jnp.stack(ks_), jnp.stack(vs_)
    x = _norm(cfg, x, params["ln_final"])
    logits = _unembed(cfg, params, x)[:, 0].astype(jnp.float32)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return {"k": knew, "v": vnew}, nxt, logits
