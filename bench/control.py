"""The control for ``correct``: the program with its exact post-filter
replaced by the answer a faster post-filter would be tempted to give,
every line of every candidate batch.  It breaks the configuration's
guarantee (exact answers), so the check has to fail it.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n>...

Runs the cell once per seed in one process, on the chip, with the
control in the program's place, and prints each run's checks.  The
benchmark's own runs never run it; ``bench/tests/test_bench_faults.py``
runs it on the CPU at a test's size.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def every_line_of_candidates(store) -> None:
    """Answer each query with every line of its candidate batches."""
    exact = store._post_filter

    def post_filter(candidates, term, mode):
        res = exact(candidates, term, mode)
        res.matches = [i for b in candidates
                       for i in range(store.batch_start[int(b)],
                                      store.batch_start[int(b) + 1])]
        return res
    store._post_filter = post_filter


def main() -> int:
    import argparse
    from bench import harness
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], args.workload, "workload")
    devices = harness.require_chip(cell["chips"])
    for seed in args.seeds:
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               devices, time.monotonic(), bench=bench,
                               fault=every_line_of_candidates)
        print(json.dumps({"control": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main())
