"""Benchmark driver: one function per paper table/figure + systems suites.

Prints ``name,us_per_call,derived`` CSV rows (assignment deliverable d) and
writes one machine-readable ``BENCH_<name>.json`` per suite (rows + config)
to ``--out-dir`` so successive PRs have a perf trajectory to diff.
``--quick`` shrinks problem sizes for CI-style runs (the streaming suite's
smoke mode).
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller problem sizes")
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument("--out-dir", default=".",
                    help="directory for BENCH_<name>.json files")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        cleanup_bench,
        common,
        fig2_effective_rate,
        serve_bench,
        sharded_bench,
        streaming_bench,
        table2_insertion,
        table3_lookup,
        table4_count_range,
    )

    benches = {
        "table2": lambda: table2_insertion.run(log_n=16 if args.quick else 20,
                                               log_bs=(12, 13) if args.quick else (12, 14, 16)),
        "table3": lambda: table3_lookup.run(log_n=14 if args.quick else 18,
                                            log_bs=(11, 12) if args.quick else (14, 16)),
        "table4": lambda: table4_count_range.run(log_n=13 if args.quick else 16,
                                                 log_bs=(10, 11) if args.quick else (12, 14),
                                                 nq=512 if args.quick else 4096),
        "fig2": lambda: fig2_effective_rate.run(log_b=11 if args.quick else 14,
                                                num_batches=16 if args.quick else 48),
        "cleanup": lambda: cleanup_bench.run(log_n=14 if args.quick else 18,
                                             log_b=11 if args.quick else 14),
        "sharded": lambda: sharded_bench.run(log_b=10 if args.quick else 11,
                                             num_batches=8 if args.quick else 16,
                                             nq=512 if args.quick else 2048),
        "streaming": lambda: streaming_bench.run(smoke=args.quick),
        "serve": lambda: serve_bench.run(smoke=args.quick),
    }
    selected = args.only.split(",") if args.only else list(benches)
    print("name,us_per_call,derived")
    for name in selected:
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        common.begin_suite(name, quick=args.quick)
        try:
            benches[name]()
        except BaseException:
            common.abort_suite()  # don't leak the recorder into later suites
            raise
        path = common.end_suite(args.out_dir)
        print(f"# {name} done in {time.time() - t0:.1f}s -> {path}", flush=True)


if __name__ == "__main__":
    main()
