"""Failure schedule of the degraded YCSB-A mix.

Set-up runs the recovery pass once (every server in turn failed with
eager batched recovery, then restored) so that its decode shapes are
compiled, then fails the server holding the most sealed chunks for the
warm-up traffic and restores it.  The window opens with the timed pass,
whose ``fail_server`` wall times give ``recover_s`` and whose rebuilt
chunks are kept for the reference; then the busiest server fails again
and stays down for the traffic.  After the window it is restored.
"""
from __future__ import annotations

import time

from bench import reference


def busiest(cluster) -> int:
    sealed = [sum(s.sealed) for s in cluster.servers]
    return max(range(len(sealed)), key=sealed.__getitem__)


def recovery_pass(ctx, timed: bool) -> None:
    cluster = ctx.cluster
    times = []
    for sid in range(len(cluster.servers)):
        t0 = time.perf_counter()
        cluster.fail_server(sid, recover=True)
        times.append(time.perf_counter() - t0)
        if timed:
            ctx.recovery_snapshots += reference.snapshot_recovery(
                cluster, sid, ctx.client.acked)
        cluster.restore_server(sid)
    if timed:
        ctx.recover_s = times


def setup(ctx) -> None:
    recovery_pass(ctx, timed=False)
    ctx.failed_server = busiest(ctx.cluster)
    ctx.cluster.fail_server(ctx.failed_server, recover=True)


def after_warmup(ctx) -> None:
    ctx.cluster.restore_server(ctx.failed_server)


def open_window(ctx) -> None:
    recovery_pass(ctx, timed=True)
    ctx.cluster.fail_server(ctx.failed_server, recover=True)


def close_window(ctx) -> None:
    ctx.cluster.restore_server(ctx.failed_server)
