"""Benchmark command: run one cell of ``BENCHMARK.json`` once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell
asks for; it exits non-zero, printing no result, anywhere else.  The
last line of standard output is the result as one JSON object.
"""
import os
import sys
import time

T_PROC = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_proc=T_PROC))
