"""Runtime substrate: optimizers, gradient compression, checkpointing,
elastic re-meshing, straggler detection, distributed sketch probe, and
the dry-run helpers."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp


# ------------------------------------------------------------- optimizers
def _quad_losses(update_fn, init_fn, cfg, steps=120):
    target = jnp.asarray([1.0, -2.0, 0.5])
    params = {"w": jnp.zeros((3, 1), jnp.float32),
              "b": jnp.zeros((3,), jnp.float32)}
    state = init_fn(params)

    def loss(p):
        return jnp.sum((p["w"][:, 0] + p["b"] - target) ** 2)

    losses = []
    for _ in range(steps):
        g = jax.grad(loss)(params)
        params, state, _ = update_fn(cfg, params, g, state)
        losses.append(float(loss(params)))
    return losses


def test_adamw_converges():
    from repro.optim.adam import AdamConfig, adam_update, init_adam
    losses = _quad_losses(adam_update, init_adam,
                          AdamConfig(lr=5e-2, warmup_steps=1))
    assert losses[-1] < 1e-2 * losses[0]


def test_adafactor_converges():
    from repro.optim.adafactor import (AdafactorConfig, adafactor_update,
                                       init_adafactor)
    cfg = AdafactorConfig(lr=5e-2, warmup_steps=1, mu_dtype="float32")
    losses = _quad_losses(adafactor_update,
                          lambda p: init_adafactor(cfg, p), cfg)
    assert losses[-1] < 5e-2 * losses[0]


def test_adafactor_state_is_factored():
    from repro.optim.adafactor import AdafactorConfig, init_adafactor
    p = {"w": jnp.zeros((64, 32)), "e": jnp.zeros((8, 16, 24))}
    st = init_adafactor(AdafactorConfig(), p)
    assert st.vr["w"].shape == (64,)
    assert st.vc["w"].shape == (32,)
    assert st.vr["e"].shape == (8, 16)
    assert st.vc["e"].shape == (8, 24)
    # bf16 first moment: 2 bytes/param instead of 8 for Adam
    assert st.mu["w"].dtype == jnp.bfloat16


# ------------------------------------------------------------ compression
def test_error_feedback_quantization_unbiased():
    """Accumulated dequantized grads track accumulated true grads — the
    error-feedback guarantee."""
    from repro.optim.compress import (dequantize_int8,
                                      quantize_with_feedback)
    rng = np.random.default_rng(0)
    err = jnp.zeros((256,), jnp.float32)
    total_true = np.zeros(256)
    total_sent = np.zeros(256)
    for step in range(50):
        g = jnp.asarray(rng.normal(size=256) * (1 + step % 5), jnp.float32)
        q, scale, err = quantize_with_feedback(g, err)
        total_true += np.asarray(g)
        total_sent += np.asarray(dequantize_int8(q, scale))
    # residual bounded by one quantization step, not growing with steps
    resid = np.abs(total_true - total_sent).max()
    assert resid <= float(np.abs(np.asarray(err)).max()) + 1e-4


def test_compressed_psum_shard_map():
    from repro.optim.compress import compressed_psum
    mesh = jax.make_mesh((1,), ("pod",))
    g = jnp.asarray(np.random.default_rng(1).normal(size=64), jnp.float32)
    err = jnp.zeros_like(g)
    from jax.sharding import PartitionSpec as P
    with jax.set_mesh(mesh):
        out, new_err = jax.shard_map(
            lambda g, e: compressed_psum(g, e, "pod"),
            in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False)(g, err)
    np.testing.assert_allclose(np.asarray(out + new_err), np.asarray(g),
                               atol=1e-4)


# ----------------------------------------------------------- checkpointing
def test_checkpoint_roundtrip_and_manifest(tmp_path):
    from repro.launch.checkpoint import CheckpointManager
    cm = CheckpointManager(str(tmp_path), keep_last=2)
    state = {"a": jnp.arange(10), "b": {"c": jnp.ones((3, 3))}}
    for step in (5, 10, 15):
        cm.save(step, jax.tree.map(lambda x: x * step, state),
                blocking=True)
    assert cm.latest_step() == 15
    restored, step = cm.restore(state)
    assert step == 15
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.arange(10) * 15)
    # retention: only keep_last remain
    ckpts = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert len(ckpts) == 2


def test_checkpoint_async_then_wait(tmp_path):
    from repro.launch.checkpoint import CheckpointManager
    cm = CheckpointManager(str(tmp_path))
    cm.save(1, {"x": jnp.ones(4)})
    cm.wait()
    assert cm.latest_step() == 1


# ----------------------------------------------------------------- elastic
def test_largest_mesh_after_failures():
    from repro.launch.elastic import largest_mesh_for
    assert largest_mesh_for(256, 16) == (16, 16)
    assert largest_mesh_for(255, 16) == (8, 16)   # lost a node: shrink DP
    assert largest_mesh_for(512, 16) == (32, 16)


def test_remesh_state_roundtrip():
    from jax.sharding import PartitionSpec as P
    from repro.launch.elastic import make_mesh_from_devices, remesh_state
    devs = jax.devices()
    mesh = make_mesh_from_devices(devs, (1, 1))
    state = {"w": np.arange(16.0).reshape(4, 4)}
    spec = {"w": P("data", None)}
    out = remesh_state(state, spec, mesh)
    np.testing.assert_array_equal(np.asarray(out["w"]), state["w"])


def test_straggler_monitor_flags():
    from repro.launch.elastic import StragglerMonitor
    m = StragglerMonitor(straggler_factor=3.0)
    for _ in range(10):
        assert not m.record(0.1)
    assert m.record(1.0)      # 10x median -> straggler
    assert m.flagged == 1


def test_health_state():
    from repro.launch.elastic import HealthState
    h = HealthState(8)
    h.fail(3)
    assert h.survivors() == 7


# ------------------------------------------------------------- distributed
def test_sharded_engine_smoke(rng):
    """The sharded probe path (full suite: tests/test_distributed.py)
    answers a wave bit-identically to its own host oracle on whatever
    mesh is visible."""
    from repro.core.batch_builder import build_sealed
    from repro.core.distributed import ShardedQueryEngine
    from repro.core.immutable_sketch import build_immutable
    fps = (rng.integers(0, 500, 4000).astype(np.uint64)
           * 2654435761 % (1 << 32)).astype(np.uint32)
    posts = rng.integers(0, 40, 4000).astype(np.int64)
    segs = [build_immutable(build_sealed(fps[i::2], posts[i::2]))
            for i in range(2)]
    eng = ShardedQueryEngine(segs, n_postings=40)
    uniq = np.unique(fps)
    queries = [[int(x) for x in uniq[:3]], [int(uniq[4])]]
    got = eng.query_fps_batch(queries)
    for q, g in zip(queries, got):
        np.testing.assert_array_equal(g, eng.host_query(q))


# ------------------------------------------------------------ dryrun utils
def test_collective_parser():
    from repro.launch.dryrun import parse_collectives
    hlo = """
      %ar = bf16[64,128]{1,0} all-reduce(%x), replica_groups={}
      %ag.1 = f32[256]{0} all-gather(%y), dimensions={0}
      %junk = f32[2] add(%a, %b)
      %rs = (f32[16], f32[16]) reduce-scatter(%z, %w)
    """
    st = parse_collectives(hlo)
    assert st["per_op"]["all-reduce"]["count"] == 1
    assert st["per_op"]["all-reduce"]["bytes"] == 64 * 128 * 2
    assert st["per_op"]["all-gather"]["bytes"] == 256 * 4
    assert st["per_op"]["reduce-scatter"]["bytes"] == 2 * 16 * 4
    # wire model: AR counts 2x
    assert st["wire_bytes_per_device"] == (2 * 64 * 128 * 2
                                           + 256 * 4 + 2 * 16 * 4)


def test_roofline_terms_math():
    from repro.launch.dryrun import roofline_terms
    t = roofline_terms(197e12, 819e9, 50e9, 256)
    assert abs(t["compute_s"] - 1.0) < 1e-9
    assert abs(t["memory_s"] - 1.0) < 1e-9
    assert abs(t["collective_s"] - 1.0) < 1e-9


def test_analysis_variant_divisibility():
    from repro.configs import get_arch
    from repro.launch.steps import analysis_variant
    spec = get_arch("arctic-480b")
    spec2, shape2, scale = analysis_variant(spec, "train_4k", 2)
    assert spec2.config.n_layers == 2
    assert not spec2.config.scan_layers
    assert shape2.dims["batch"] * scale == 256


# ---------------------------------------------------------- data pipeline
def test_pipeline_deterministic_resume(small_dataset):
    from repro.data import LMTokenPipeline
    p1 = LMTokenPipeline(small_dataset.lines, vocab=512, batch=4, seq=16,
                         seed=7)
    p2 = LMTokenPipeline(small_dataset.lines, vocab=512, batch=4, seq=16,
                         seed=7)
    # any step reproducible from (seed, step): exact resume + elasticity
    for step in (0, 5, 17):
        a, b = p1.batch_at(step), p2.batch_at(step)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        np.testing.assert_array_equal(a["labels"], b["labels"])
    assert p1.batch_at(1)["tokens"].shape == (4, 16)
    assert (p1.batch_at(0)["tokens"] != p1.batch_at(1)["tokens"]).any()


def test_sketch_filtered_corpus(small_dataset):
    from repro.data import SketchFilteredCorpus
    from repro.logstore.store import DynaWarpStore
    store = DynaWarpStore(batch_lines=64)
    store.ingest(small_dataset.lines)
    store.finish()
    sel = SketchFilteredCorpus(store, include_terms=("error",))
    batches = sel.selected_batches()
    assert 0 < len(batches) < store.n_batches
    # every selected shard really contains the term (post-filter truth)
    got_lines = list(sel.lines())
    assert got_lines and any("ERROR" in l or "error" in l
                             for l in got_lines)
    # exclusion removes those shards
    none = SketchFilteredCorpus(store, include_terms=("error",),
                                exclude_terms=("error",))
    assert len(none.selected_batches()) == 0
