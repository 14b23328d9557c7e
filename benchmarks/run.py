"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes one machine-readable
``BENCH_<suite>.json`` per suite (name, µs, derived metrics, config) so the
perf trajectory is tracked across PRs. The dry-run-derived roofline tables
live in benchmarks/roofline.py (they need results/ from repro.launch.dryrun).

    PYTHONPATH=src python -m benchmarks.run             # all CPU benches
    PYTHONPATH=src python -m benchmarks.run --only fig3
    PYTHONPATH=src python -m benchmarks.run --smoke     # CI-sized inputs
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmarks import common

SUITES = ("table2", "fig3", "fig4", "threshold", "kernels", "batch", "serve")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated suite names")
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI-sized inputs: every suite shrinks its shapes/iterations",
    )
    ap.add_argument(
        "--out", default=".", help="directory for the BENCH_<suite>.json files"
    )
    args = ap.parse_args()
    wanted = tuple(args.only.split(",")) if args.only else SUITES

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    os.makedirs(args.out, exist_ok=True)
    print("name,us_per_call,derived")
    for name in wanted:
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        t0 = time.time()
        mod.run(smoke=args.smoke)
        elapsed = time.time() - t0
        path = os.path.join(args.out, f"BENCH_{name}.json")
        with open(path, "w") as f:
            json.dump(
                {
                    "suite": name,
                    "smoke": args.smoke,
                    "suite_s": round(elapsed, 1),
                    "rows": common.drain_records(),
                },
                f,
                indent=1,
            )
        print(f"# suite {name} finished in {elapsed:.1f}s -> {path}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
