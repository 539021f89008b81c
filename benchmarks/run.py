"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only redundancy,...] [--fast]

Emits ``name,us_per_call,derived`` CSV rows per experiment plus the
per-table detail rows.  ``--fast`` (equivalently ``MEMEC_BENCH_FAST=1``)
trims every sweep that supports it to its CI smoke variant — the shape
``scripts/verify.sh --ci`` captures into ``BENCH_ci.json``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

MODULES = ["redundancy", "throughput", "coding_schemes", "value_sizes",
           "degraded", "transitions", "rebalance", "kernels_bench",
           "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(MODULES))
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke variant (sets MEMEC_BENCH_FAST=1)")
    args = ap.parse_args()
    from repro.kernels import dispatch
    dispatch.enable_compile_cache()
    if args.fast:
        os.environ["MEMEC_BENCH_FAST"] = "1"
    selected = args.only.split(",") if args.only else MODULES
    for name in selected:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        print(f"\n=== {name} ===", flush=True)
        t0 = time.time()
        mod.run()
        print(f"=== {name} done in {time.time() - t0:.1f}s ===", flush=True)


if __name__ == '__main__':
    main()
