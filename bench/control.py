#!/usr/bin/env python3
"""Readings that set the limits of a cell's comparison, for many seeds in
one process.

    python3 bench/control.py --workload paper-update --seconds 3 --seeds 11 12 13

For each seed it runs the cell (set-up, a short window at the cell's own
load, the check) and prints one JSON line: the numbers the program's run
gave, and those the control gives when its answers replace the program's.
The control is the reference holding its values in int16, one step below
the configurations' exact 32-bit values (bench/reference.py): every limit
must pass the program's runs and fail the control's. The benchmark's own
runs never run it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import harness

    t_start = T_START
    for seed in args.seeds:
        out = harness.run(args.workload, seed, args.seconds, False, t_start=t_start,
                          control=True)
        t_start = time.perf_counter()
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": out["result"]["correct"],
            "program": {c.name: c.value for c in out["checks"]},
            "control": {c.name: c.value for c in out["control"]},
            "control_fails": not all(c.ok for c in out["control"]),
            "metrics": {k: v["value"] for k, v in out["result"]["metrics"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
