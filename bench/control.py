#!/usr/bin/env python3
"""Run a cell sound and with a fault planted, for the readings that set
the limits of ``correct``; the benchmark's own runs never do this.

    python bench/control.py --workload rs-a --seeds 1,2,3 --seconds 10

runs each seed once sound and once with the control fault
(``bench.faults.CONTROL``, or those named by ``--faults``), all in one
process, and prints one JSON line per run: the seed, the fault, whether
the run came out correct, and every number compared with its limit.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", default=None,
                    help="comma-separated faults (default: the control)")
    ap.add_argument("--no-sound", action="store_true",
                    help="skip the runs with no fault")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(REPO))
    from bench.run import configure
    refused = configure()
    if refused:
        print(f"bench: refused: {refused}", file=sys.stderr)
        return 2
    from bench import faults
    from bench.harness import run_cell

    planted = args.faults.split(",") if args.faults else [faults.CONTROL]
    runs = ([] if args.no_sound else [None]) + planted
    for seed in (int(s) for s in args.seeds.split(",")):
        for fault in runs:
            r = run_cell(args.workload, seed, args.seconds, False,
                         fault=fault)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "fault": fault,
                "correct": r["correct"], "attempted": r["attempted"],
                "failed": r["failed"],
                "checks": {k: v["value"] for k, v in r["checks"].items()},
                "metrics": {k: v["value"] for k, v in r["metrics"].items()}}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
