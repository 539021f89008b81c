#!/usr/bin/env python3
"""Run one benchmark cell and print its result line.

    python bench/run.py --workload rs-a --seed 7 --seconds 30 --trace 0

from the repository root.  The last line of standard output is the JSON
result (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``: each number
compared with the reference beside its limit); the same checks end
standard error.  The run refuses, printing no result and exiting
non-zero, when any ``MEMEC_*`` variable is set, when JAX finds no TPU or
fewer chips than the cell asks for, or when the kernels would not run
compiled.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
# JAX's persistent compilation cache and the TPU runtime's logs stay
# inside the checkout, at fixed paths (the cache's path is part of its key)
CACHE_DIR = BENCH / ".jax_cache"
TPU_LOG_DIR = BENCH / ".tpu_logs"


def configure() -> str | None:
    """Refuse what the benchmark must refuse (returns the reason), else
    put the store on the path, fix the compile cache and the TPU logs
    inside the checkout and turn the cache on."""
    knobs = sorted(k for k in os.environ if k.startswith("MEMEC_"))
    if knobs:
        return (f"{', '.join(knobs)} set; the benchmark passes every store "
                f"option itself")
    if not (REPO / "src" / "repro").is_dir():
        return f"the store's sources are not under {REPO / 'src'}"
    for p in (str(REPO / "src"), str(REPO)):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["TPU_LOG_DIR"] = str(TPU_LOG_DIR)
    from repro.kernels import dispatch
    dispatch.enable_compile_cache()
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    refused = configure()
    if refused:
        print(f"bench: refused: {refused}", file=sys.stderr)
        return 2
    from bench.harness import BenchFailure, run_cell
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except BenchFailure as e:
        print(f"bench: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
