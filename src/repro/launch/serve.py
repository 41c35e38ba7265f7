"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

``--arch dynawarp`` (alias ``copr``) runs the log-store serving loop:
a :class:`~repro.core.serving.StoreServer` wave scheduler over a store
(freshly built, or ``--store <dir>`` to open a durable one), driven by
a pool of concurrent clients; prints q/s, p50/p99 latency, and wave
coalescing stats.  Knobs: ``--clients``, ``--requests`` (per client),
``--replicas``, ``--max-live-waves``, ``--flush-deadline-ms``,
``--cost-model <json>`` (from ``benchmarks/query_throughput.py``),
``--profile-dir DIR`` (a profiler trace of the client phase, with the
``copr.*`` spans of ``repro.tracing``, for TensorBoard or Perfetto).

LM archs: prefill a batch of prompts, then greedy-decode N tokens with
the KV cache (the same prefill/decode_step the dry-run lowers at 32k).
RecSys archs: batched scoring loop (serve kind) with latency stats.
Runs the reduced smoke config on CPU; --full targets the pod mesh.
"""
from __future__ import annotations

import argparse
import sys
import time
from contextlib import nullcontext

import numpy as np


def _serve_dynawarp(args) -> int:
    import os
    import threading

    from ..core.serving import CostModel
    from ..logstore.datasets import (generate_dataset, id_queries,
                                     present_id_queries)
    from ..logstore.store import DynaWarpStore

    if args.store:
        store = DynaWarpStore.open(args.store)
        print(f"[serve] opened store {args.store}: "
              f"{store.n_batches} batches, "
              f"{len(store.segments)} segments", flush=True)
        terms = id_queries(5, 16)       # contents unknown: generic probes
    else:
        ds = generate_dataset("serve", n_lines=args.lines, n_sources=24,
                              seed=11)
        store = DynaWarpStore(batch_lines=64, mode="segmented",
                              memory_limit_bytes=1 << 15)
        store.ingest(ds.lines)
        store.finish()
        print(f"[serve] built store: {store.n_batches} batches, "
              f"{len(store.segments)} segments", flush=True)
        terms = present_id_queries(ds, 5, 16)

    cost_model = None
    if args.cost_model and os.path.exists(args.cost_model):
        cost_model = CostModel.load(args.cost_model)
        print(f"[serve] cost model {args.cost_model}: "
              f"host {cost_model.host_us_per_query:.0f} us/query",
              flush=True)

    server = store.serving(n_replicas=args.replicas,
                           max_live_waves=args.max_live_waves,
                           flush_deadline_s=args.flush_deadline_ms / 1e3,
                           cost_model=cost_model)
    lat: list[list[float]] = [[] for _ in range(args.clients)]

    def client(ci: int) -> None:
        rng = np.random.default_rng(ci)
        for _ in range(args.requests):
            term = terms[int(rng.integers(len(terms)))]
            t0 = time.perf_counter()
            server.query_term(term, timeout=120)
            lat[ci].append(time.perf_counter() - t0)

    server.query_term(terms[0], timeout=300)          # warm-up/compile
    threads = [threading.Thread(target=client, args=(ci,), daemon=True)
               for ci in range(args.clients)]
    profile = nullcontext()
    if args.profile_dir:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the program's spans, not Python's
        profile = jax.profiler.trace(args.profile_dir,
                                     create_perfetto_trace=True,
                                     profiler_options=opts)
    with profile:
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        dt = time.perf_counter() - t0
    server.close()
    if args.profile_dir:
        print(f"[serve] profiler trace under {args.profile_dir}",
              flush=True)

    lat_ms = np.asarray([x for per in lat for x in per]) * 1e3
    st = server.scheduler.stats()
    n = len(lat_ms)
    print(f"[serve] {n} queries from {args.clients} clients in {dt:.2f}s "
          f"({n / dt:.1f} q/s)  p50 {np.percentile(lat_ms, 50):.2f}ms  "
          f"p99 {np.percentile(lat_ms, 99):.2f}ms", flush=True)
    print(f"[serve] {st.waves} waves ({st.host_waves} host / "
          f"{st.device_waves} device; {st.size_flushes} size / "
          f"{st.deadline_flushes} deadline flushes), max wave "
          f"{st.max_wave}, replicas used: "
          f"{sorted(st.replica_waves)}", flush=True)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--decode-tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    # log-store serving knobs (--arch dynawarp)
    ap.add_argument("--store", default=None,
                    help="durable store directory to open (dynawarp)")
    ap.add_argument("--lines", type=int, default=6_000)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--max-live-waves", type=int, default=2)
    ap.add_argument("--flush-deadline-ms", type=float, default=2.0)
    ap.add_argument("--cost-model", default=None,
                    help="bench_costmodel.json from query_throughput")
    ap.add_argument("--profile-dir", default=None,
                    help="write a profiler trace of the client phase "
                         "here (dynawarp)")
    args = ap.parse_args(argv)
    from ..compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.arch in ("dynawarp", "copr"):
        if args.requests == 8:          # store default differs from LM
            args.requests = 25
        return _serve_dynawarp(args)

    import jax
    import jax.numpy as jnp

    from ..configs import get_arch
    from ..launch.steps import family_init

    spec = get_arch(args.arch)
    cfg = spec.smoke_config
    params = family_init(spec, smoke=True)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    if spec.family == "lm":
        from ..models.transformer import decode_step, init_cache, prefill
        B, S = args.batch, args.prompt_len
        max_len = S + args.decode_tokens
        prompts = jnp.asarray(rng.integers(1, cfg.vocab, (B, S)), jnp.int32)

        pf = jax.jit(lambda p, t: prefill(cfg, p, t))
        dec = jax.jit(
            lambda p, c, t, n: decode_step(cfg, p, c, t, n),
            static_argnames=())
        t0 = time.perf_counter()
        cache_pref, logits = pf(params, prompts)
        cache = init_cache(cfg, B, max_len, cfg.compute_dtype)
        cache = {
            "k": cache["k"].at[:, :, :S].set(cache_pref["k"]),
            "v": cache["v"].at[:, :, :S].set(cache_pref["v"]),
        }
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out_tokens = [tok]
        for i in range(args.decode_tokens - 1):
            cache, tok, _ = jax.jit(
                lambda p, c, t, i=S + i: decode_step(cfg, p, c, t, i)
            )(params, cache, tok)
            out_tokens.append(tok)
        dt = time.perf_counter() - t0
        gen = jnp.stack(out_tokens, 1)
        print(f"[serve] generated {gen.shape} tokens in {dt:.2f}s "
              f"({B * args.decode_tokens / dt:.1f} tok/s incl. compile)")
        print("[serve] sample:", np.asarray(gen[0][:8]))
        return 0

    # recsys batched scoring
    from ..launch.steps import serve_fn
    from dataclasses import replace as dc_replace
    smoke_spec = dc_replace(spec, config=cfg)
    shape = spec.shapes["serve_p99"]
    fn = jax.jit(serve_fn(smoke_spec, shape))
    lat = []
    for r in range(args.requests):
        batch = spec.smoke_batch(cfg, np.random.default_rng(r))
        if "cand" not in batch and spec.id != "xdeepfm" \
                and spec.id != "two-tower-retrieval":
            batch["cand"] = jnp.asarray(
                np.random.default_rng(r).integers(
                    1, getattr(cfg, "n_items", 100), (len(next(iter(
                        batch.values()))), 32)), jnp.int32)
        t0 = time.perf_counter()
        if spec.id == "xdeepfm":
            from ..models.recsys import xdeepfm_logits
            scores = xdeepfm_logits(cfg, params, batch["idx"])
        elif spec.id == "two-tower-retrieval":
            from ..models.recsys import twotower_serve
            scores = twotower_serve(cfg, params, batch)
        else:
            scores = fn(params, batch)
        scores.block_until_ready()
        lat.append(time.perf_counter() - t0)
    lat_ms = np.asarray(lat[1:]) * 1e3  # drop compile
    print(f"[serve] {args.requests} requests; p50 {np.percentile(lat_ms, 50):.2f}ms "
          f"p99 {np.percentile(lat_ms, 99):.2f}ms scores {scores.shape}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
