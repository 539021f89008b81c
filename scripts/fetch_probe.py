#!/usr/bin/env python3
"""Time one jitted RS parity-delta call's wait and fetch, with and without
the device-to-host copy started at dispatch.

    python scripts/fetch_probe.py [--calls 200] [--batch 2] [--out FILE]

from the repository root, on a TPU.  For each chunk size C in 128, 1024
and 4096 it calls ``delta_apply_batched(parity, gammas, xors)`` with host
operands of the engine's RS shape (B items, m = 2), alternating two ways
of bringing the result home:

* ``late``: ``block_until_ready`` then ``np.asarray`` (the copy is asked
  for only after the computation is waited for);
* ``early``: ``copy_to_host_async()`` right after the call, then the same
  two steps.

It prints one JSON line with the median enqueue, wait and fetch times in
ms per way and per C, and the result bytes per call.  Off the TPU it
refuses, printing no result.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import dispatch  # noqa: E402
from repro.kernels.delta_update import delta_apply_batched  # noqa: E402

CHUNKS = (128, 1024, 4096)
M = 2


def one_call(parity, gammas, xors, early: bool) -> tuple[float, float, float]:
    """(enqueue, wait, fetch) seconds of one call."""
    t0 = time.perf_counter()
    dev = delta_apply_batched(parity, gammas, xors)
    if early:
        dev.copy_to_host_async()
    t1 = time.perf_counter()
    jax.block_until_ready(dev)
    t2 = time.perf_counter()
    np.asarray(dev)
    t3 = time.perf_counter()
    return t1 - t0, t2 - t1, t3 - t2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--calls", type=int, default=200)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    dec = dispatch.decide()
    if dec.path != dispatch.PALLAS:
        print(f"kernels would run on {dec.path}, not compiled Pallas",
              file=sys.stderr)
        return 1
    rng = np.random.default_rng(0)
    B = args.batch
    result = {"device": jax.devices()[0].device_kind, "batch": B, "m": M,
              "calls": args.calls, "cells": []}
    for C in CHUNKS:
        parity = rng.integers(0, 256, (B, M, C), dtype=np.uint8)
        gammas = rng.integers(1, 256, (B, M)).astype(np.int32)
        xors = rng.integers(0, 256, (B, C), dtype=np.uint8)
        for _ in range(10):                        # compile and warm up
            one_call(parity, gammas, xors, early=False)
            one_call(parity, gammas, xors, early=True)
        times = {"late": [], "early": []}
        for i in range(2 * args.calls):
            way = "late" if i % 2 == 0 else "early"
            times[way].append(one_call(parity, gammas, xors, way == "early"))
        cell = {"C": C, "d2h_bytes": B * M * C}
        for way, ts in times.items():
            enq, wait, fetch = zip(*ts)
            cell[way] = {
                "enqueue_ms": statistics.median(enq) * 1e3,
                "wait_ms": statistics.median(wait) * 1e3,
                "fetch_ms": statistics.median(fetch) * 1e3,
                "total_ms": statistics.median(
                    e + w + f for e, w, f in ts) * 1e3,
            }
        result["cells"].append(cell)
    line = json.dumps(result)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
