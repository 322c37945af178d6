#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload paper-update --seed 7 --seconds 20 --trace 0

The cell, its configuration, its traffic mix and its per-layer metrics are
found by name from BENCHMARK.json (see bench/harness.py). With --trace 0 the
result carries the cell's end-to-end metrics; with --trace 1 a profiler
trace of the window gives its per-layer metrics. The last line of standard
output is one JSON object; the numbers compared with the reference are the
last lines of standard error. Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.

JAX's persistent compilation cache is kept in `.jax_cache/` inside the
checkout, so that only a checkout's first run of a cell compiles.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness

    return harness.main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
