"""Runs one cell of ``BENCHMARK.json`` once and prints its result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name in
``BENCHMARK.json`` (or in the config or traffic file that names it), so
a new deployment arrives as new files and edits none:

  * ``bench/configs/<config>.json``: the deployment.  ``corpus``: the
    generator's settings, and optionally ``generator``, which names
    ``bench/corpora/<generator>.py`` (without it, ``bench/corpus.py``);
    ``store``: ``DynaWarpStore`` settings of the store as built;
    optionally ``open``: keyword arguments of ``DynaWarpStore.open``
    (lists become tuples, e.g. ``"shard_axes": ["data"]``);
    ``serving``: ``store.serving`` settings; ``guarantee``: what every
    answer is held to;
  * ``bench/corpora/<generator>.py``: ``generate(**corpus settings) ->
    bench.corpus.Corpus`` (``lines``, one string per line, and
    ``sources``, the source of each line, are what scenarios read),
    the same lines for the same settings;
  * ``bench/traffic/<traffic>.json``: the load (loop kind, rate or
    clients, the query pool by scenario, and optionally ``pool_seed``,
    which draws one pool for every ``--seed``);
  * ``bench/queries/<scenario>.py``: a scenario ``bench/scenarios.py``
    does not define: ``OP`` and ``draw(rng, corpus, n, traffic) ->
    list[str]``, and for an op the harness does not know, ``tokens(text)``,
    ``send(server, text, timeout)`` and ``answer(lines, texts) ->
    {text: ids}`` (``bench/ops.py``);
  * ``bench/metrics/<metric>.py`` (or ``<part before the first dot>.py``):
    ``read(run) -> float | None`` for one per-layer metric.

A run: generate the configuration's corpus (fixed by its ``corpus.seed``:
the deployment's data), open the cached store (build it first on a new
key), draw the query pool and the arrivals from ``--seed``, start
``store.serving``, warm every shape the pool can form, then drive the
traffic for ``--seconds`` (traced with ``--trace 1``), read the device's
peak memory, free the program's state, and compare every answer of the
window with the plain reference.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from dataclasses import dataclass

import numpy as np

from . import arrivals, byname, corpus as corpus_mod, loops, measure, ops
from . import scenarios, store_cache

BENCH_DIR = store_cache.BENCH_DIR
ROOT = store_cache.ROOT
TRACE_DIR = os.path.join(store_cache.CACHE_DIR, "trace")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def find(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def require_chip(n_chips: int):
    """The devices of a TPU with at least ``n_chips`` chips, or exit."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX platform "
                         f"{devices[0].platform!r}); the benchmark runs on "
                         f"a TPU only")
    if len(devices) < n_chips:
        raise SystemExit(f"bench: the cell needs {n_chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:n_chips]


class CompileClock:
    """JAX backend compiles (persistent-cache reads included), with the
    time of each, and persistent-cache hits and misses."""

    def __init__(self):
        from jax import monitoring
        self.stamps: list[float] = []
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.stamps.append(time.monotonic())

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def between(self, a: float, b: float) -> int:
        return sum(a <= t <= b for t in self.stamps)


@dataclass
class Run:
    """What a per-layer reader may read."""
    cell: str
    config: dict
    traffic: dict
    queries: list               # (scenario, op, text)
    n_tokens: list              # program tokens of each query
    records: list               # loops.Record of every request sent
    stats: dict                 # scheduler counters moved in the window
    n_batches: int
    device_kind: str
    t0: float
    t_end: float
    trace: dict | None = None

    @property
    def words(self) -> int:
        return -(-self.n_batches // 32)

    def answered(self) -> list:
        return [r for r in self.records if r.done and not r.error]


def make_corpus(cfg: dict) -> corpus_mod.Corpus:
    """The configuration's corpus, from its generator."""
    params = dict(cfg["corpus"])
    name = params.pop("generator", None)
    gen = byname.load("corpora", name) if name else corpus_mod
    return gen.generate(**params)


def open_settings(cfg: dict) -> dict:
    """Keyword arguments of ``DynaWarpStore.open``, lists as tuples."""
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in cfg.get("open", {}).items()}


def _pow2(n: int, lo: int) -> int:
    return 1 << (max(n, lo) - 1).bit_length()


def q_buckets(config: dict, traffic: dict) -> list[int]:
    """The Q buckets the cell's waves can take.  No more queries are in
    flight than the open loop's ``workers`` or the closed loop's
    ``clients``, so a wave holds at most that many: the buckets up to the
    first that holds them."""
    buckets = sorted(config["serving"]["bucket_sizes"])
    most = traffic["workers" if traffic["loop"] == "open" else "clients"]
    cap = next((b for b in buckets if b >= most), buckets[-1])
    return [b for b in buckets if b <= cap]


def warm(server, token_lists: list, buckets: list[int]) -> None:
    """Compile every shape the cell can form: for each T bucket of the
    pool and each Q bucket, one wave per candidate-count bucket that a
    pool query reaches (a wave's extract width is that of its largest
    answer), on every engine replica."""
    engines = server.scheduler.engines
    groups: dict[int, list[int]] = {}
    for i, toks in enumerate(token_lists):
        if toks:
            groups.setdefault(_pow2(len(toks), 1), []).append(i)
    top = buckets[-1]
    for tb, idx in sorted(groups.items()):
        counts = {}
        for a in range(0, len(idx), top):
            chunk = idx[a:a + top]
            got = engines[0].query_batch([token_lists[i] for i in chunk])
            counts.update({i: len(c) for i, c in zip(chunk, got)})
        reps = {}
        for i in idx:
            reps.setdefault(_pow2(counts[i], 8) if counts[i] else 0, i)
        for eng in engines:
            for qb in buckets:
                for i in reps.values():
                    eng.query_batch([token_lists[i]] * qb)


def _serve_once(send, queries: list) -> None:
    """One request of each scenario through the server's own threads."""
    seen = set()
    for scenario, op, text in queries:
        if scenario not in seen:
            seen.add(scenario)
            send(op, text)


def _answers(config_name: str, cfg: dict, traffic_name: str,
             traffic: dict, seed: int, queries: list, lines,
             op_table: dict) -> dict:
    """Reference answers for every pool query, from the cache or a scan
    (``ops.answers``)."""
    want = {(op, text) for _, op, text in queries}
    files = scenarios.module_files(traffic)
    have = store_cache.load_answers(config_name, cfg, traffic_name, seed,
                                    files)
    if want <= set(have):
        return have
    answers = ops.answers(op_table, lines(), want)
    store_cache.save_answers(config_name, cfg, traffic_name, seed, files,
                             answers)
    return answers


def check(records: list, queries: list, answers: dict) -> dict:
    """Every answer of the window against the reference: the numbers
    compared, each with its limit (exact comparisons: 0)."""
    differing = missing = 0
    for r in records:
        if not r.done or r.error:
            missing += 1
            continue
        _, op, text = queries[r.query]
        want = np.asarray(answers[(op, text)], np.int64)
        if not np.array_equal(np.sort(r.matches), want):
            differing += 1
    return {"answers_differing": {"value": differing, "limit": 0},
            "answers_missing": {"value": missing, "limit": 0}}


def end_to_end(name: str, run: Run, extra: dict) -> float:
    if name == "setup_s":
        return extra["setup_s"]
    if name == "query_p99_ms":
        lat = [((r.done if r.done and not r.error
                 else r.due + loops.LATE_S) - r.due) * 1e3
               for r in run.records]
        return measure.percentile(lat, 99)
    if name == "queries_per_s":
        return measure.rate([r.done for r in run.answered()],
                            run.t0, run.t_end)
    if name == "stored_bytes_per_raw_byte":
        return extra["stored_bytes"] / extra["raw_bytes"]
    raise SystemExit(f"bench: no arithmetic for end-to-end metric {name!r}")


def load_reader(name: str):
    for stem in (name, name.split(".")[0]):
        if os.path.exists(byname.path("metrics", stem)):
            return byname.load("metrics", stem).read
    raise SystemExit(f"bench: no reader bench/metrics/{name}.py")


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             devices, t_proc: float, *, bench: dict | None = None,
             overrides: dict | None = None, fault=None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``overrides`` (tests only) merges into the configuration and the
    traffic; ``fault`` (tests only) is called with the open store before
    serving, to break the timed path underneath."""
    bench = bench or load_benchmark()
    cell = find(bench["workloads"], cell_name, "workload")
    config_name, traffic_name = cell["config"], cell["traffic"]
    cfg = load_json(os.path.join(BENCH_DIR, "configs",
                                 f"{config_name}.json"))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     f"{traffic_name}.json"))
    for key, val in (overrides or {}).get("config", {}).items():
        cfg[key] = {**cfg[key], **val} if isinstance(val, dict) else val
    traffic.update((overrides or {}).get("traffic", {}))
    if (overrides or {}).get("config"):
        config_name += "-test"

    from repro.compile_cache import enable_compile_cache
    from repro.logstore.store import DynaWarpStore
    enable_compile_cache()
    clock = CompileClock()
    last = time.monotonic()
    parts = {"start": last - t_proc}      # set-up's parts, for the log

    def part(name: str) -> None:
        nonlocal last
        now = time.monotonic()
        parts[name], last = now - last, now

    corpus = make_corpus(cfg)
    part("corpus")
    path = store_cache.store_path(config_name, cfg, lambda: corpus.lines)
    raw_bytes = corpus.raw_bytes()
    stored = store_cache.stored_bytes(path)
    part("store")
    queries, op_table = scenarios.pool(traffic, corpus, seed)
    token_lists = [op_table[op].tokens(text) for _, op, text in queries]
    part("pool")
    store = DynaWarpStore.open(path, **open_settings(cfg))
    if fault is not None:
        fault(store)
    n_batches = store.n_batches
    server = store.serving(**cfg["serving"])
    part("open")

    def send(op, text):
        return op_table[op].send(server, text, loops.LATE_S)
    try:
        warm(server, token_lists, q_buckets(cfg, traffic))
        part("warm")
        _serve_once(send, queries)
        stats0 = server.scheduler.stats()
        part("serve_once")
        records, t0, t_end, marks, tr = _drive(
            server, send, queries, traffic, seed, seconds, trace)
        setup_s = t0 - t_proc
        log("set-up " + ", ".join(f"{k} {v:.2f} s"
                                  for k, v in parts.items())
            + f", window start {t0 - t_proc - sum(parts.values()):.2f} s")
    finally:
        server.close()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    store.close()
    del store, server
    stats = {k: getattr(marks["stats"], k) - getattr(stats0, k)
             for k in ("submitted", "completed", "failed", "waves",
                       "host_waves", "device_waves", "padded_slots")}
    log(f"window: {len(records)} requests, {stats}, compiles in window "
        f"{clock.between(t0, t_end)}, compile {clock.compile_s:.1f} s "
        f"({clock.hits} cache hits, {clock.misses} misses)")

    t_ref = time.monotonic()
    answers = _answers(config_name, cfg, traffic_name, traffic, seed,
                       queries, lambda: corpus.lines, op_table)
    checks = check(records, queries, answers)
    log(f"reference and check {time.monotonic() - t_ref:.1f} s")

    run = Run(cell=cell_name, config=cfg, traffic=traffic, queries=queries,
              n_tokens=[len(t) for t in token_lists], records=records,
              stats=stats, n_batches=n_batches,
              device_kind=devices[0].device_kind, t0=t0, t_end=t_end,
              trace=tr.get("trace"))
    _report_host(run)
    extra = {"setup_s": setup_s, "raw_bytes": raw_bytes,
             "stored_bytes": stored}
    metrics = {}
    if trace:
        for m in bench["per_layer"]:
            if applies(m, cell_name):
                val = load_reader(m["name"])(run)
                if val is not None:
                    metrics[m["name"]] = {"value": float(val),
                                          "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if applies(m, cell_name):
                metrics[m["name"]] = {"value": float(end_to_end(
                    m["name"], run, extra)), "unit": m["unit"]}
    failed = sum(1 for r in records if not r.done or r.error)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    out = {"correct": correct, "attempted": len(records), "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        from . import trace as trace_mod
        device["busy_s"] = trace_mod.busy_s(run.trace)
        device["window_s"] = trace_mod.window_s(run.trace)
        out["breakdown"] = {"device_ops": trace_mod.top_ops(run.trace),
                            "idle_gaps": trace_mod.idle_gaps(run.trace)}
    out["checks"] = checks
    return out


def _drive(server, send, queries, traffic, seed, seconds, trace):
    """The measured window: load in a thread of its own, each request
    through ``send(op, text)``, the scheduler's counters read at the
    window's close, the trace (if any) stopped there."""
    from contextlib import nullcontext
    from . import trace as trace_mod
    span = None
    if trace:
        import jax
        span = jax.profiler.TraceAnnotation
    t0 = time.monotonic() + 0.5
    t_end = t0 + seconds
    box: dict = {}
    marks: dict = {}
    if traffic["loop"] == "open":
        due, order = arrivals.open_schedule(
            len(queries), traffic["rate_qps"], seconds, seed)
        records = loops.open_records(due, order, t0)

        def load():
            box["records"] = loops.open_loop(
                send, queries, records, workers=traffic["workers"],
                span=span)
    else:
        seqs = arrivals.closed_sequences(
            [q[0] for q in queries], traffic["clients"],
            traffic["sequence_length"], seed)

        def load():
            box["records"] = loops.closed_loop(
                send, queries, seqs, t0, seconds, span=span)

    def guarded():
        try:
            load()
        except BaseException as e:      # reported by the main thread
            box["error"] = e

    tr: dict = {}
    worker = threading.Thread(target=guarded, name="bench-load")
    ctx = (trace_mod.capture(TRACE_DIR, tr) if trace else nullcontext())
    with ctx:
        worker.start()
        time.sleep(max(t_end - time.monotonic(), 0))
        marks["stats"] = server.scheduler.stats()
    worker.join(seconds + 2 * loops.LATE_S)
    if worker.is_alive():
        raise RuntimeError("the load did not finish")
    if "error" in box:
        raise box["error"]
    return box["records"], t0, t_end, marks, tr


def _report_host(run: Run) -> None:
    """Earlier lines: the p50, the generator's lateness, the answers'
    sizes.  None of these is a metric."""
    ok = run.answered()
    if not ok:
        return
    lat = sorted((r.done - r.due) * 1e3 for r in ok)
    late = sorted((r.start - r.due) * 1e3 for r in run.records if r.start)
    log(f"latency p50 {measure.percentile(lat, 50):.3f} ms, p99 "
        f"{measure.percentile(lat, 99):.3f} ms, max {lat[-1]:.3f} ms over "
        f"{len(lat)} answers; sends late by p50 "
        f"{measure.percentile(late, 50):.3f} ms, p99 "
        f"{measure.percentile(late, 99):.3f} ms")
    by: dict[str, list] = {}
    for r in ok:
        by.setdefault(run.queries[r.query][0], []).append(r)
    for scen, rs in sorted(by.items()):
        log(f"{scen}: {len(rs)} answers, latency mean "
            f"{np.mean([(r.done - r.due) * 1e3 for r in rs]):.3f} ms, "
            f"candidate batches mean {np.mean([r.candidates for r in rs]):.1f}"
            f", matches mean {np.mean([len(r.matches) for r in rs]):.1f}")


def main(argv=None, t_proc: float | None = None) -> int:
    import argparse
    t_proc = time.monotonic() if t_proc is None else t_proc
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark()
    cell = find(bench["workloads"], args.workload, "workload")
    devices = require_chip(cell["chips"])
    out = run_cell(args.workload, args.seed, args.seconds,
                   bool(args.trace), devices, t_proc, bench=bench)
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0
