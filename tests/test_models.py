"""Per-arch smoke tests (deliverable f): every assigned architecture
instantiates its REDUCED config and runs one forward/train step on CPU,
asserting output shapes and finiteness.  Plus family-specific behaviour
tests (decode==prefill, MoE dispatch equivalence, capsule routing)."""
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_arch
from repro.launch.steps import family_init, family_loss


@pytest.mark.parametrize("arch_id", sorted(ARCHS))
def test_arch_smoke_step(arch_id):
    """One loss+grad step on the reduced config: finite loss, finite
    grads, param shapes preserved."""
    spec = get_arch(arch_id)
    cfg = spec.smoke_config
    smoke_spec = replace(spec, config=cfg)
    params = family_init(spec, smoke=True)(jax.random.PRNGKey(0))
    batch = spec.smoke_batch(cfg, np.random.default_rng(0))
    loss_fn = family_loss(smoke_spec)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
    assert np.isfinite(float(loss)), arch_id
    leaves = jax.tree.leaves(grads)
    assert leaves and all(bool(jnp.all(jnp.isfinite(g))) for g in leaves)
    # shapes preserved through one update
    for p, g in zip(jax.tree.leaves(params), leaves):
        assert p.shape == g.shape


def test_lm_decode_matches_prefill():
    from repro.models.transformer import (decode_step, init_cache,
                                          prefill)
    spec = get_arch("gemma2-9b")
    cfg = spec.smoke_config
    params = family_init(spec, smoke=True)(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0, cfg.vocab)
    cache_p, logits_p = prefill(cfg, params, toks)
    full = init_cache(cfg, 2, 32, jnp.float32)
    full["k"] = full["k"].at[:, :, :24].set(cache_p["k"])
    full["v"] = full["v"].at[:, :, :24].set(cache_p["v"])
    nxt = jnp.argmax(logits_p, -1).astype(jnp.int32)
    _, _, logits_d = decode_step(cfg, params, full, nxt, 24)
    toks_ext = jnp.concatenate([toks, nxt[:, None]], 1)
    _, logits_p2 = prefill(cfg, params, toks_ext)
    np.testing.assert_allclose(np.asarray(logits_d),
                               np.asarray(logits_p2), rtol=2e-4, atol=2e-4)


def test_moe_sharded_equals_global_on_unit_mesh():
    """shard_map expert dispatch == global sort dispatch when the expert
    axis has size 1 (same capacity semantics)."""
    from repro.models.moe import moe_ffn, moe_ffn_sharded
    rng = jax.random.PRNGKey(0)
    t, d, e, f = 64, 16, 8, 32
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (t, d), jnp.float32)
    w = {"router": jax.random.normal(ks[1], (d, e)) * 0.1,
         "w_gate": jax.random.normal(ks[2], (e, d, f)) * 0.1,
         "w_up": jax.random.normal(ks[3], (e, d, f)) * 0.1,
         "w_down": jax.random.normal(ks[4], (e, f, d)) * 0.1}
    y_ref, aux_ref = moe_ffn(x, w, n_experts=e, top_k=2,
                             capacity_factor=8.0)  # no drops
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        y_sm, aux_sm = jax.jit(lambda x, w: moe_ffn_sharded(
            x, w, n_experts=e, top_k=2, capacity_factor=8.0,
            batch_axes=("data",), expert_axis="model",
            expert_parallel=1))(x, w)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_sm),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux_ref), float(aux_sm), rtol=1e-5)


def test_sliding_window_masks_old_positions():
    """A local-attention layer must ignore tokens beyond the window."""
    from repro.models.attention import blockwise_attention
    rng = jax.random.PRNGKey(0)
    b, s, h, dh = 1, 32, 2, 8
    q = jax.random.normal(rng, (b, s, h, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, dh))
    out_w = blockwise_attention(q, k, v, causal=True, window=4,
                                q_chunk=8, kv_chunk=8)
    # perturb a key far outside every query's window: no output change
    k2 = k.at[:, 0].add(100.0)
    v2 = v.at[:, 0].add(100.0)
    out_w2 = blockwise_attention(q, k2, v2, causal=True, window=4,
                                 q_chunk=8, kv_chunk=8)
    np.testing.assert_allclose(np.asarray(out_w[:, 8:]),
                               np.asarray(out_w2[:, 8:]), atol=1e-5)


def test_blockwise_equals_naive_attention():
    from repro.models.attention import blockwise_attention
    rng = jax.random.PRNGKey(3)
    b, s, hq, hkv, dh = 2, 40, 4, 2, 8
    q = jax.random.normal(rng, (b, s, hq, dh))
    k = jax.random.normal(jax.random.PRNGKey(4), (b, s, hkv, dh))
    v = jax.random.normal(jax.random.PRNGKey(5), (b, s, hkv, dh))
    out = blockwise_attention(q, k, v, causal=True, q_chunk=16, kv_chunk=8)
    # naive reference
    from repro.models.attention import repeat_kv
    kk, vv = repeat_kv(k, 2), repeat_kv(v, 2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(dh)
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask[None, None], sc, -1e30)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), vv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_gnn_permutation_invariance():
    """segment_sum aggregation: permuting edge order never changes the
    output (sum aggregator property)."""
    from repro.models import gnn
    cfg = get_arch("meshgraphnet").smoke_config
    params = gnn.init_params(cfg, jax.random.PRNGKey(0))
    g = get_arch("meshgraphnet").smoke_batch(cfg, np.random.default_rng(1))
    out1 = gnn.forward(cfg, params, g)
    perm = np.random.default_rng(2).permutation(g["senders"].shape[0])
    g2 = dict(g)
    for k in ("edges", "senders", "receivers", "edge_mask"):
        g2[k] = g[k][perm]
    out2 = gnn.forward(cfg, params, g2)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-4, atol=1e-5)


def test_neighbor_sampler_validity():
    from repro.models.gnn import neighbor_sample
    rng = np.random.default_rng(0)
    n = 50
    indptr = np.arange(0, 4 * (n + 1), 4)
    indices = rng.integers(0, n, 4 * n)
    nodes, snd, rcv = neighbor_sample(indptr, indices, [0, 1], [3, 2], rng)
    assert set(nodes[:2]) == {0, 1}
    assert snd.max(initial=0) < len(nodes)
    assert rcv.max(initial=0) < len(nodes)
    # every sampled edge exists in the CSR graph
    for s, r in zip(snd[:20], rcv[:20]):
        u, v = int(nodes[r]), int(nodes[s])
        assert v in indices[indptr[u]:indptr[u + 1]]


def test_mind_interests_shape_and_squash():
    from repro.models import recsys
    cfg = get_arch("mind").smoke_config
    params = recsys.mind_init(cfg, jax.random.PRNGKey(0))
    seq = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.n_items, (4, cfg.seq_len)), jnp.int32)
    u = recsys.mind_interests(cfg, params, seq)
    assert u.shape == (4, cfg.n_interests, cfg.embed_dim)
    assert bool(jnp.all(jnp.isfinite(u)))


def test_twotower_inbatch_loss_decreases():
    from repro.models import recsys
    from repro.optim.adam import AdamConfig, adam_update, init_adam
    cfg = get_arch("two-tower-retrieval").smoke_config
    spec = get_arch("two-tower-retrieval")
    params = recsys.twotower_init(cfg, jax.random.PRNGKey(0))
    opt = init_adam(params)
    ocfg = AdamConfig(lr=3e-3, warmup_steps=1)
    batch = spec.smoke_batch(cfg, np.random.default_rng(0))

    @jax.jit
    def step(params, opt):
        (l, _), g = jax.value_and_grad(
            lambda p: recsys.twotower_loss(cfg, p, batch),
            has_aux=True)(params)
        params, opt, _ = adam_update(ocfg, params, g, opt)
        return params, opt, l

    losses = []
    for _ in range(30):
        params, opt, l = step(params, opt)
        losses.append(float(l))
    assert losses[-1] < losses[0], losses[:3] + losses[-3:]


def test_seq_parallel_attention_equivalence():
    """It. 7 (EXPERIMENTS.md §Perf): sequence-parallel attention core ==
    blockwise attention, including causal offsets across shards."""
    import os
    from repro.models.attention import (blockwise_attention,
                                        seq_parallel_attention)
    b, s, hq, hkv, dh = 2, 64, 7, 1, 8   # non-divisible heads (arctic-like)
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, hq, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, dh))
    want = blockwise_attention(q, k, v, causal=True, q_chunk=16,
                               kv_chunk=16)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        got = jax.jit(lambda q, k, v: seq_parallel_attention(
            q, k, v, batch_axes=("data",), model_axis="model",
            causal=True, q_chunk=16, kv_chunk=16))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
