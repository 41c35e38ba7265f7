"""Chip smoke test: serve a 1M-line COPR store from one TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # the sharded engine over four chips

One process, no children.  It checks the device first and exits non-zero
unless JAX runs on a TPU (there is no CPU fallback).  Then it generates
``--lines`` log lines from ``--seed``, ingests them into a durable
segmented ``DynaWarpStore`` in a temporary directory, finishes and
closes it, and reopens it with ``DynaWarpStore.open``.

Default path: ``store.serving(n_replicas=2)`` serves the paper's five
query scenarios (``benchmarks/common.py``) from 8 client threads, 256
queries, through device waves of at least 8 query slots.
Every answer must equal ``ScanStore`` over the same lines, every segment
must carry bitmap planes, and every wave must run on the device.

``--chips 4``: the store reopens with ``shard_axes=('data',)``, so its
``ShardedQueryEngine`` spreads the segments' probes over four devices.
The scenarios' distinct queries are served through it, their candidates
are compared with a single-device ``QueryEngine`` on device 0, and their
answers with ``ScanStore``.

Earlier lines report sizes, wave counts, compile counts, device memory
and smoke timings (host wall clock; they are not benchmark metrics).
The last line is one JSON object, ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import jax

ROOT = os.path.dirname(os.path.abspath(__file__))
N_CLIENTS = 8
MIN_QUERIES = 256


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu(n_chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform "
                 f"{devices[0].platform!r}); this check runs on a TPU only")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} devices, "
                 f"JAX sees {len(devices)}")
    return devices


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache reads
    included) and counts persistent-cache hits and misses."""

    def __init__(self):
        from jax import monitoring
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def build_store(args, tmp: str):
    """Generate, ingest durably, finish, close; return (dataset, path)."""
    from repro.logstore.datasets import generate_dataset
    from repro.logstore.store import DynaWarpStore
    t0 = time.perf_counter()
    ds = generate_dataset("smoke", n_lines=args.lines, n_sources=160,
                          seed=args.seed)
    t1 = time.perf_counter()
    path = os.path.join(tmp, "store")
    store = DynaWarpStore(batch_lines=512, mode="segmented", path=path)
    store.ingest(ds.lines)
    store.finish()
    store.close()
    log(f"smoke timing (not a metric): generate {t1 - t0:.1f} s, "
        f"ingest+finish {time.perf_counter() - t1:.1f} s")
    return ds, path


def query_mix(ds, server, scan, *, fill: bool) -> list[tuple]:
    """(scenario, term, served call, reference call) for every distinct
    query of the paper's five scenarios.  ``fill`` cycles the scenarios
    whose answers are small up to MIN_QUERIES queries in all.  A
    term(extracted) query matches a large share of all lines and its host
    post-filter alone takes seconds, so those are served once each."""
    sys.path.insert(0, ROOT)
    from benchmarks.common import QUERY_SCENARIOS
    base = []
    for name, make in QUERY_SCENARIOS.items():
        terms, served = make(ds, server)
        _, ref = make(ds, scan)
        base += [(name, t, served, ref) for t in terms]
    if not fill:
        return base
    cheap = [q for q in base if q[0] != "term(extracted)"]
    n_extra = max(MIN_QUERIES - len(base), 0)
    return base + (cheap * -(-n_extra // len(cheap)))[:n_extra]


def serve(server, mix) -> list:
    """Answer ``mix`` from N_CLIENTS threads; returns the match lists."""
    answers: list = [None] * len(mix)
    errors: list = []
    nxt = iter(range(len(mix)))
    lock = threading.Lock()

    def client():
        while True:
            with lock:
                i = next(nxt, None)
            if i is None:
                return
            _, term, served, _ = mix[i]
            try:
                answers[i] = sorted(served(term, timeout=900).matches)
            except BaseException as e:      # reported, then fails the run
                errors.append(f"{term!r}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=client, name=f"client-{c}")
               for c in range(N_CLIENTS)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if errors:
        raise RuntimeError(f"{len(errors)} queries failed, first: "
                           f"{errors[0]}")
    return answers


def check_answers(mix, answers) -> int:
    """Compare every served answer with the ScanStore reference; returns
    the number of distinct queries checked."""
    ref: dict = {}
    bad = []
    for (name, term, _, scan_fn), got in zip(mix, answers):
        key = (name, term)
        if key not in ref:
            ref[key] = sorted(scan_fn(term).matches)
        if got != ref[key]:
            bad.append(f"{name} {term!r}: served {len(got)} lines, "
                       f"scan {len(ref[key])}")
    if bad:
        raise AssertionError(f"{len(bad)} answers differ from ScanStore, "
                             f"first: {bad[0]}")
    return len(ref)


def plane_report(store) -> int:
    """Fails on plane-less segments; returns plane bytes on the device."""
    planeless = sum(seg.planes is None for seg in store.segments)
    if planeless:
        raise AssertionError(f"{planeless} of {len(store.segments)} "
                             f"segments have no bitmap planes (host probe)")
    return sum(int(seg.device_cache()["planes"].nbytes)
               for seg in store.segments)


def served_waves(server) -> dict:
    st = server.scheduler.stats()
    log(f"waves: {st.waves} ({st.device_waves} device, {st.host_waves} "
        f"host), largest {st.max_wave} queries, {st.padded_slots} padded "
        f"slots, replicas {st.replica_waves}")
    if st.device_waves == 0 or st.host_waves > 0:
        raise AssertionError(f"device_waves={st.device_waves}, "
                             f"host_waves={st.host_waves}: every wave must "
                             f"run on the device")
    return dict(device_waves=st.device_waves, host_waves=st.host_waves)


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def serve_and_check(args, tmp: str, *, shard_axes, fill: bool):
    """Build and reopen the store, serve the query mix through
    ``store.serving(n_replicas=2)``, and check every answer against
    ``ScanStore``.  Returns the open store and the mix."""
    from repro.logstore.store import DynaWarpStore, ScanStore
    t0 = time.perf_counter()
    ds, path = build_store(args, tmp)
    store = DynaWarpStore.open(path, shard_axes=shard_axes)
    scan = ScanStore(batch_lines=512, batch_cache_size=1 << 20)
    scan.ingest(ds.lines)
    scan.finish()
    t_setup = time.perf_counter() - t0
    log(f"{ds.n_lines} lines, {store.n_batches} batches, "
        f"{len(store.segments)} segments")
    server = store.serving(n_replicas=2, flush_deadline_s=0.005)
    try:
        mix = query_mix(ds, server, scan, fill=fill)
        t0 = time.perf_counter()
        answers = serve(server, mix)
        t_serve = time.perf_counter() - t0
        waves = served_waves(server)
        compiles = sum(e.compile_count for e in server.scheduler.engines)
    finally:
        server.close()
    t0 = time.perf_counter()
    n_distinct = check_answers(mix, answers)
    t_check = time.perf_counter() - t0
    plane_bytes = plane_report(store)
    peaks = [peak_bytes(d) for d in jax.devices()[:args.chips]]
    log(f"{len(mix)} queries ({n_distinct} distinct) from {N_CLIENTS} "
        f"clients equal ScanStore; {waves}")
    log(f"plane bytes on device {plane_bytes}, engine compile_count "
        f"{compiles}, peak_bytes_in_use per device {peaks}")
    log(f"smoke timing (not a metric): set-up {t_setup:.1f} s, "
        f"serve {t_serve:.1f} s, ScanStore check {t_check:.1f} s")
    return store, mix


def run_one_chip(args, tmp: str) -> None:
    store, _ = serve_and_check(args, tmp, shard_axes=None, fill=True)
    store.close()


def run_four_chips(args, tmp: str) -> None:
    from repro.core.query_engine import QueryEngine
    from repro.core.tokenizer import (contains_query_tokens,
                                      term_query_tokens)
    store, mix = serve_and_check(args, tmp, shard_axes=("data",),
                                 fill=False)
    sharded = store.engine
    busy = probe_shards(sharded)
    # candidates: sharded engine vs one QueryEngine on device 0, in waves
    # of the served bucket size (the shapes the server already compiled)
    with jax.default_device(jax.devices()[0]):
        single = QueryEngine(store.segments, n_postings=store.n_batches)
        token_lists = [term_query_tokens(t) if name.startswith("term")
                       else contains_query_tokens(t)
                       for name, t, _, _ in mix]
        token_lists = [tl for tl in token_lists if tl]
        for i in range(0, len(token_lists), 8):
            wave = token_lists[i:i + 8]
            got = sharded.query_batch(wave)
            want = single.query_batch(wave)
            for g, w in zip(got, want):
                if list(g) != list(w):
                    raise AssertionError("sharded candidates differ from "
                                         "the single-device engine")
    store.close()
    log(f"probe rows on {busy} of {sharded.n_shards} shard devices; "
        f"{len(token_lists)} candidate sets equal the device-0 engine")


def probe_shards(engine) -> int:
    """Number of shard devices that hold a live segment row: the probe
    runs where the rows are, so this many devices share the probe work.
    Fails unless every segment sits on its own device, as far as there
    are devices."""
    busy = set()
    for key, seg_ids in engine._buckets:
        garrs, _ = engine._bucket_global(key, seg_ids)
        for shard in garrs["active"].addressable_shards:
            if int(jax.device_get(shard.data).sum()) > 0:
                busy.add(shard.device.id)
    want = min(len(engine._plane_segs), engine.n_shards)
    if len(busy) < want:
        raise AssertionError(f"segment probes on {len(busy)} devices, "
                             f"expected {want}")
    return len(busy)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--lines", type=int, default=1_000_000)
    ap.add_argument("--seed", type=int, default=2)
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    log(f"device {devices[0].device_kind} x{len(devices)}, compile cache "
        f"{cache_dir}")
    with tempfile.TemporaryDirectory(prefix="copr-smoke-") as tmp:
        if args.chips == 4:
            run_four_chips(args, tmp)
        else:
            run_one_chip(args, tmp)
    log(f"compile time {clock.compile_s:.1f} s, persistent cache "
        f"{clock.cache_hits} hits / {clock.cache_misses} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
