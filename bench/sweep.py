#!/usr/bin/env python3
"""Find a cell's knee: one set-up, then short open-loop windows at rising
offered rates, in one process.

    python bench/sweep.py --workload rs-a --seed 11 --seconds 8 \\
        --rates 1000,2000,3000

Each rate prints one JSON line: offered and completed ops/s, the backlog
(ops due but not yet issued) at the middle and at the end of the window,
ops still unserved when the window closed, p50/p99 latency, device
calls and data-chunk seals per op, and wrong reads.  The knee is the
highest rate at which completed keeps up with offered, the backlog does
not grow from the middle to the end, and no op is left unissued.  A
cell whose traffic has a failure schedule runs its window-opening step
once before the first rate.  The sweep stops after the first rate that
completes under nine tenths of what it offered.

``--traffic-overrides`` replaces top-level values of the cell's traffic
file, so the knee of a mix that no cell names yet can be found on a
cell's configuration without adding the cell::

    python bench/sweep.py --workload rs-a --seed 11 --seconds 8 \\
        --rates 4000,6000 --traffic-overrides '{"mix": {"insert": 1.0}}'

Each rate's inserts take the ids after the warm-up's and the previous
rate's, so no key is inserted twice.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


def backlog(due, issue, t: float) -> int:
    """Ops due by ``t`` and not issued by then (NaN: never issued)."""
    import numpy as np
    return int(((due <= t) & ~(np.nan_to_num(issue, nan=np.inf) <= t)).sum())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, ops/s")
    ap.add_argument("--traffic-overrides", type=json.loads, default=None,
                    help="JSON object of traffic values to replace")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(REPO))
    from bench.run import configure
    refused = configure()
    if refused:
        print(f"bench: refused: {refused}", file=sys.stderr)
        return 2
    import numpy as np
    from bench.driver import drive
    from bench.harness import prepare, sealed_data_chunks, warm_up
    from bench.stream import make_stream, rng_for

    rates = [float(r) for r in args.rates.split(",")]
    cell = prepare(args.workload, args.seed,
                   traffic_overrides=args.traffic_overrides)
    cfg, traffic = cell.cfg, cell.traffic
    warm = make_stream(cfg, traffic, rates[0], traffic["warmup_s"],
                       rng_for(args.seed, 1))
    streams, first = [], cfg["objects"] + warm.inserts
    for i, r in enumerate(rates):
        streams.append(make_stream(cfg, traffic, r, args.seconds,
                                   rng_for(args.seed, 3 + i),
                                   first_insert_id=first))
        first += streams[-1].inserts
    warm_up(cell, [warm] + streams)
    if cell.hook is not None:
        cell.hook.open_window(cell.ctx)
    T = args.seconds
    engine = cell.cluster.engine
    for rate, s in zip(rates, streams):
        c0 = cell.counter.compiles
        d0, sealed0 = engine.device_dispatches, sealed_data_chunks(cell.cluster)
        rec = drive(cell.client, s, max_window=traffic["max_window_ops"],
                    num_proxies=cfg["num_proxies"],
                    engine=engine, deadline_s=2.0)
        lat = rec.done - s.due
        done = ~np.isnan(lat)
        completed = float((rec.done <= T).sum()) / T
        row = {"rate": rate, "completed_per_s": completed,
               "backlog_mid": backlog(s.due, rec.issue, T / 2),
               "backlog_end": backlog(s.due, rec.issue, T),
               "unserved": int((~done).sum()),
               "p50_ms": 1e3 * float(np.percentile(lat[done], 50)),
               "p99_ms": 1e3 * float(np.percentile(lat[done], 99)),
               "mean_window_ops": float(np.mean([w[2] for w in rec.windows])),
               "compiles": cell.counter.compiles - c0,
               "device_calls_per_op":
                   (engine.device_dispatches - d0) / len(s),
               "seals_per_op":
                   (sealed_data_chunks(cell.cluster) - sealed0) / len(s),
               "wrong_reads": cell.client.wrong_reads,
               "refused": cell.client.refused}
        print(json.dumps(row), flush=True)
        if completed < 0.9 * rate:
            break
    if cell.hook is not None:
        cell.hook.close_window(cell.ctx)
    return 0


if __name__ == "__main__":
    sys.exit(main())
