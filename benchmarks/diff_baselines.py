"""Compare fresh BENCH_<suite>.json runs against committed baselines.

Usage:
    python -m benchmarks.diff_baselines --current bench-out \\
        [--baseline benchmarks/baselines] [--threshold 3.0] [--update]

For every suite present in BOTH directories, each row's `us_per_call` is
compared by name. A row regresses when current > threshold * baseline; the
exit code is 1 if any row regresses (the CI perf lane fails on it). New rows
(no baseline) and removed rows are reported but never fail the diff — suites
grow across PRs.

The threshold is deliberately generous (default 3.0x): shared-CI wall-clock
noise on the CPU is large, and this lane exists to catch
order-of-magnitude regressions (an accidentally quadratic path, a lost jit
cache), not single-digit percent drift. Tighten it when runners are
dedicated.

`--update` rewrites the baseline directory from the current run (the
workflow for intentional perf-profile changes: regenerate, review the JSON
diff, commit).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys


def load_suites(dir_path: str) -> dict:
    suites = {}
    for path in sorted(glob.glob(os.path.join(dir_path, "BENCH_*.json"))):
        with open(path) as f:
            payload = json.load(f)
        suites[payload["suite"]] = payload
    return suites


def diff_suite(name: str, base: dict, cur: dict, threshold: float):
    """Yield (row_name, status, detail) for one suite."""
    base_rows = {r["name"]: r for r in base["rows"]}
    cur_rows = {r["name"]: r for r in cur["rows"]}
    for row_name in sorted(set(base_rows) | set(cur_rows)):
        b, c = base_rows.get(row_name), cur_rows.get(row_name)
        if b is None:
            yield row_name, "new", f"{c['us_per_call']:.1f}us (no baseline)"
            continue
        if c is None:
            yield row_name, "removed", f"baseline was {b['us_per_call']:.1f}us"
            continue
        if b["us_per_call"] <= 0:
            yield row_name, "ok", "baseline 0us, skipped"
            continue
        ratio = c["us_per_call"] / b["us_per_call"]
        detail = (
            f"{b['us_per_call']:.1f}us -> {c['us_per_call']:.1f}us "
            f"({ratio:.2f}x)"
        )
        yield row_name, ("regressed" if ratio > threshold else "ok"), detail


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--current", required=True,
                    help="directory with freshly generated BENCH_*.json")
    ap.add_argument("--baseline", default="benchmarks/baselines",
                    help="directory with committed baseline BENCH_*.json")
    ap.add_argument("--threshold", type=float, default=3.0,
                    help="fail when current > threshold * baseline us_per_call")
    ap.add_argument("--update", action="store_true",
                    help="rewrite the baseline directory from --current")
    args = ap.parse_args()

    current = load_suites(args.current)
    if args.update:
        os.makedirs(args.baseline, exist_ok=True)
        for path in sorted(glob.glob(os.path.join(args.current, "BENCH_*.json"))):
            shutil.copy(path, args.baseline)
            print(f"updated {os.path.join(args.baseline, os.path.basename(path))}")
        return 0

    baseline = load_suites(args.baseline)
    if not baseline:
        print(f"no baselines in {args.baseline!r}; nothing to diff")
        return 0

    regressions = 0
    for name in sorted(set(baseline) & set(current)):
        bb, cc = baseline[name], current[name]
        if bb.get("config") != cc.get("config"):
            print(f"[{name}] config changed {bb.get('config')} -> "
                  f"{cc.get('config')}; skipping (regenerate baselines)")
            continue
        for row_name, status, detail in diff_suite(
            name, bb, cc, args.threshold
        ):
            marker = {"ok": " ", "new": "+", "removed": "-", "regressed": "!"}[status]
            print(f"[{name}] {marker} {row_name}: {detail}")
            if status == "regressed":
                regressions += 1
    missing = sorted(set(baseline) - set(current))
    if missing:
        print(f"suites not re-run (kept baselines): {missing}")
    if regressions:
        print(f"FAIL: {regressions} row(s) regressed beyond "
              f"{args.threshold:.1f}x", file=sys.stderr)
        return 1
    print("perf diff OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
