"""Run one benchmark cell and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cells, metrics and bounds are in BENCHMARK.json; bench/harness.py says
what a run does.  Exits non-zero, printing no result, where JAX finds no
accelerator or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    harness.process_env()
    sys.exit(harness.main(t_start=T_START))
