"""Benchmark harness: one module per paper table/figure.

  ingest_speed      — Fig. 4
  disk_usage        — Fig. 5
  query_throughput  — Table 3
  error_rate        — §5.2 error rates (DynaWarp vs CSC, 4-orders claim)
  scan_rate         — §6 production scan-rate vs selectivity
  dedup_stats       — §3.2 dedup + fingerprint-memory claims
  probe_bench       — beyond-paper batched device probe
  live_tail         — beyond-paper live ingest: per-spill publish cost,
                      snapshot/live query rates, crash-recovery latency
  serve_load        — beyond-paper serving: coalesced waves vs per-query
                      dispatch under open-loop client load
  roofline          — §Roofline table from the dry-run artifact

``python -m benchmarks.run [--only name]`` writes bench_results.json.
"""
import argparse
import json
import sys
import time

from repro.compile_cache import enable_compile_cache

from . import (dedup_stats, disk_usage, error_rate, ingest_speed,
               live_tail, probe_bench, query_throughput, roofline,
               scan_rate, serve_load)

MODULES = {
    "ingest_speed": ingest_speed,
    "disk_usage": disk_usage,
    "query_throughput": query_throughput,
    "error_rate": error_rate,
    "scan_rate": scan_rate,
    "dedup_stats": dedup_stats,
    "probe_bench": probe_bench,
    "live_tail": live_tail,
    "serve_load": serve_load,
    "roofline": roofline,
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="bench_results.json")
    args = ap.parse_args(argv)
    enable_compile_cache()
    results: dict = {}
    t0 = time.time()
    for name, mod in MODULES.items():
        if args.only and name != args.only:
            continue
        print(f"=== {name} ===", flush=True)
        t = time.time()
        mod.run(results)
        print(f"=== {name} done in {time.time()-t:.1f}s ===\n", flush=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1, default=str)
    print(f"[bench] all done in {time.time()-t0:.1f}s -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
