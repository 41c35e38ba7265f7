"""Mixture-of-Experts FFN: top-k router + capacity-bounded sort dispatch.

Design notes (TPU/roofline-driven):
  * Dispatch is *sort-based* (GShard/MaxText style), not dense-einsum: the
    dense one-hot formulation multiplies HLO FLOPs by E/top_k (8x for
    phi3.5-moe, 64x for arctic), destroying the useful-FLOPs roofline term.
    Sort dispatch keeps expert GEMM FLOPs proportional to *activated*
    parameters: E * capacity * d * f with capacity ~= T*top_k/E * cf.
  * Expert weights carry a leading E dim sharded over the 'model' mesh axis
    (expert parallelism); the scatter/gather around the expert GEMM is what
    becomes the all-to-all under SPMD partitioning.
  * Tokens overflowing an expert's capacity are dropped (standard GShard
    semantics); the router keeps a load-balancing auxiliary loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def moe_ffn(x, w, *, n_experts: int, top_k: int,
            capacity_factor: float = 1.25, act=jax.nn.silu):
    """x: (T, D) tokens; w: dict(router (D, E), w_gate/w_up (E, D, F),
    w_down (E, F, D)).  Returns (out (T, D), aux_loss scalar)."""
    t, d = x.shape
    e = n_experts
    capacity = max(int(t * top_k / e * capacity_factor + 0.5), 1)
    capacity = min(capacity, t)

    logits = (x @ w["router"]).astype(jnp.float32)          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, top_k)                 # (T, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): E * mean(frac_tokens * frac_probs)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)

    # ---- sort-based dispatch -------------------------------------------
    flat_e = idx.reshape(-1)                                # (T*K,)
    flat_t = jnp.repeat(jnp.arange(t), top_k)
    flat_g = gate.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st = flat_e[order], flat_t[order]
    # position of each routed token inside its expert's queue
    ones = jnp.ones_like(se)
    pos_in_e = jnp.cumsum(ones) - 1
    start = jnp.searchsorted(se, jnp.arange(e))             # (E,)
    pos_in_e = pos_in_e - start[se]
    keep = pos_in_e < capacity
    dest = jnp.where(keep, se * capacity + pos_in_e, e * capacity)

    buf = jnp.zeros((e * capacity + 1, d), x.dtype)
    buf = buf.at[dest].set(x[st], mode="drop")
    buf = buf[:-1].reshape(e, capacity, d)                  # (E, C, D)

    # ---- expert GEMMs (sharded over 'model' on the E dim) --------------
    h = jnp.einsum("ecd,edf->ecf", buf, w["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, w["w_up"])
    y = jnp.einsum("ecf,efd->ecd", act(h) * u, w["w_down"]) # (E, C, D)

    # ---- combine back to token order ------------------------------------
    y_flat = y.reshape(e * capacity, d)
    gathered = jnp.where(keep[:, None],
                         y_flat[jnp.clip(dest, 0, e * capacity - 1)],
                         jnp.zeros((1, d), y_flat.dtype))
    sg = flat_g[order]
    contrib = gathered * sg[:, None].astype(gathered.dtype)
    out = jnp.zeros((t, d), jnp.float32).at[st].add(
        contrib.astype(jnp.float32))
    return out.astype(x.dtype), aux


# -------------------------------------------------------- sharded variant
def moe_ffn_sharded(x, w, *, n_experts: int, top_k: int,
                    capacity_factor: float = 1.25, act=jax.nn.silu,
                    batch_axes=("data",), expert_axis="model",
                    fsdp_axis=None, expert_parallel: int | None = None):
    """Expert-parallel MoE via shard_map — the 1000-node dispatch path.

    Motivation (measured, see EXPERIMENTS.md §Perf): the global sort-based
    dispatch above is correct but GSPMD cannot shard a data-dependent
    argsort/scatter over tokens, so it all-gathers every token array —
    on arctic-480b that replicated the microbatch 16x (55 GiB/chip) and
    made the step collective-bound.

    Layout contract:
      x        (T, D)    sharded P(batch_axes, None)
      router   (D, E)    replicated
      w_gate/up(E, D, F) sharded P(expert_axis, fsdp_axis, None)
      w_down   (E, F, D) sharded P(expert_axis, None, fsdp_axis)

    Device (d, m) holds token shard d (replicated over m) and expert shard
    m.  Dispatch is a purely LOCAL sort+scatter into that shard's experts
    (capacity per data-shard); the only collectives are the FSDP weight
    all-gather and one psum over the expert axis for the combine — the
    a2a pattern of GShard realized as gather-free selection because tokens
    are already replicated along the expert axis.
    """
    if expert_parallel is None:
        mesh = jax.sharding.get_abstract_mesh()
        expert_parallel = mesh.shape[expert_axis]
    m_size = expert_parallel
    e_local = n_experts // m_size
    assert e_local * m_size == n_experts, (n_experts, m_size)

    xp = P(batch_axes, None)
    wg_spec = P(expert_axis, fsdp_axis, None)
    wd_spec = P(expert_axis, None, fsdp_axis)

    def inner(xs, router, wg, wu, wd):
        tl, d = xs.shape
        if fsdp_axis is not None:
            wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_axis, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_axis, axis=2, tiled=True)
        midx = jax.lax.axis_index(expert_axis)
        capacity = max(int(tl * top_k / n_experts * capacity_factor + 0.5),
                       4)
        capacity = min(capacity, tl)

        logits = (xs @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, top_k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(idx[:, 0], n_experts,
                                     dtype=jnp.float32), axis=0)
        aux = n_experts * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, batch_axes)

        # local selection of THIS shard's experts
        flat_e = idx.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(tl), top_k)
        flat_g = gate.reshape(-1)
        rel = flat_e - midx * e_local
        local = (rel >= 0) & (rel < e_local)
        le = jnp.where(local, rel, e_local)          # e_local = drop bucket
        order = jnp.argsort(le, stable=True)
        se, st, sg = le[order], flat_t[order], flat_g[order]
        pos = (jnp.cumsum(jnp.ones_like(se)) - 1
               - jnp.searchsorted(se, jnp.arange(e_local + 1))[se])
        keep = (se < e_local) & (pos < capacity)
        dest = jnp.where(keep, se * capacity + pos, e_local * capacity)

        buf = jnp.zeros((e_local * capacity + 1, d), xs.dtype)
        buf = buf.at[dest].set(xs[st], mode="drop")
        buf = buf[:-1].reshape(e_local, capacity, d)

        h = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        y = jnp.einsum("ecf,efd->ecd", act(h) * u, wd)

        y_flat = y.reshape(e_local * capacity, d)
        gathered = jnp.where(
            keep[:, None],
            y_flat[jnp.clip(dest, 0, e_local * capacity - 1)],
            jnp.zeros((1, d), y_flat.dtype))
        contrib = gathered * sg[:, None].astype(gathered.dtype)
        out = jnp.zeros((tl, d), jnp.float32).at[st].add(
            contrib.astype(jnp.float32))
        # combine across expert shards; psum in the compute dtype halves
        # the dominant MoE wire term (top-2 partial sums per token — bf16
        # rounding of two-term sums is standard EP practice)
        out = jax.lax.psum(out.astype(xs.dtype), expert_axis)
        return out, aux

    out, aux = jax.shard_map(
        inner,
        in_specs=(xp, P(None, None), wg_spec, wg_spec, wd_spec),
        out_specs=(xp, P()),
        check_vma=False)(x, w["router"], w["w_gate"], w["w_up"],
                         w["w_down"])
    return out, aux
