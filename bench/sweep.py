"""Knee sweep of an open-loop traffic mix: the highest rate it sustains.

    python3 bench/sweep.py --config loghub-1m --traffic ioc --seed <n> --seconds <s> --rates 500 1000 ...

Runs the mix once per rate, in one process, on the chip, and prints one
``SWEEP`` line per rate and a ``KNEE`` line.  The knee is the highest rate
whose answered rate stays within 2% of the offered one, with every answer
correct; a cell's fixed ``rate_qps`` is set at about four fifths of it
where a tail judges the cell, and five fourths where the answers
completed per second do.  A knee at the highest rate tried is no knee:
sweep higher.
The mix need not be a cell of ``BENCHMARK.json`` yet: the sweep is how its
rate is found before it becomes one.  The benchmark's own runs never
sweep.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: The end-to-end metrics a sweep reads at each rate.
METRICS = [{"name": "queries_per_s", "unit": "queries/s"},
           {"name": "query_p99_ms", "unit": "ms"}]


def sweep_bench(config: str, traffic: str) -> tuple[str, dict]:
    """A one-cell benchmark of ``config`` under ``traffic`` that reports
    the sweep's metrics: ``(cell name, benchmark)``."""
    name = f"{config}.{traffic}"
    return name, {"workloads": [{"name": name, "config": config,
                                 "traffic": traffic, "chips": 1}],
                  "end_to_end": METRICS, "per_layer": []}


def main() -> int:
    import argparse
    from bench import harness
    ap = argparse.ArgumentParser(prog="bench/sweep.py")
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    cell, bench = sweep_bench(args.config, args.traffic)
    devices = harness.require_chip(1)
    knee = None
    for rate in args.rates:
        out = harness.run_cell(cell, args.seed, args.seconds, False,
                               devices, time.monotonic(), bench=bench,
                               overrides={"traffic": {"rate_qps": rate}})
        m = out["metrics"]
        qps = m["queries_per_s"]["value"]
        ok = out["correct"] and qps >= 0.98 * rate
        knee = rate if ok else knee
        print("SWEEP " + json.dumps({
            "rate": rate, "queries_per_s": qps,
            "query_p99_ms": m["query_p99_ms"]["value"],
            "correct": out["correct"], "failed": out["failed"],
            "sustained": ok}), flush=True)
    print("KNEE " + json.dumps({"knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
