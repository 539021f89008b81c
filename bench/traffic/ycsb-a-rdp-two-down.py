"""Failure schedule of YCSB-A on RDP with two servers down.

The pair failed is the two servers that appear together in the most
stripe lists (ties: the pair holding the most sealed chunks, then the
lowest ids), so that most sealed stripes lose two chunks.  Set-up fails
both with eager batched recovery, so that the two-erasure decode shapes
compile, and the warm-up traffic runs with both down; after it both are
restored.  The window opens by failing the first and then the second,
each with recovery, and keeps each one's rebuilt chunks for the
reference; the traffic runs with both down.  The harness reads every
written key back before ``close_window``, with both still down; then the
count of chunks rebuilt from two-loss stripes goes to standard error and
both are restored.
"""
from __future__ import annotations

import itertools
import json

from bench import reference
from bench.harness import CompileCounter, log


def pair(cluster) -> tuple[int, int]:
    lists = [set(sl.servers) for sl in cluster.stripe_lists]
    sealed = [sum(s.sealed) for s in cluster.servers]
    return min(itertools.combinations(range(len(sealed)), 2),
               key=lambda p: (-sum(set(p) <= sl for sl in lists),
                              -sealed[p[0]] - sealed[p[1]], p))


def fail_both(ctx, snapshot: bool) -> None:
    for sid in ctx.failed_pair:
        ctx.cluster.fail_server(sid, recover=True)
        if snapshot:
            ctx.recovery_snapshots += reference.snapshot_recovery(
                ctx.cluster, sid, ctx.client.acked)


def restore_both(ctx) -> None:
    for sid in ctx.failed_pair:
        ctx.cluster.restore_server(sid)


def setup(ctx) -> None:
    ctx.failed_pair = pair(ctx.cluster)
    ctx.compile_counter = CompileCounter()
    fail_both(ctx, snapshot=False)


def after_warmup(ctx) -> None:
    restore_both(ctx)


def open_window(ctx) -> None:
    ctx.at_open = (ctx.compile_counter.compiles,
                   ctx.cluster.stats["degraded_requests"])
    fail_both(ctx, snapshot=True)


def close_window(ctx) -> None:
    stats = ctx.cluster.stats
    compiles, degraded = ctx.at_open
    # a store without the counter (an older program) logs null
    log(json.dumps({
        "failed_servers": list(ctx.failed_pair),
        "two_loss_rebuilds": stats.get("two_loss_rebuilds"),
        "degraded_requests_since_open": stats["degraded_requests"] - degraded,
        "compiles_since_open": ctx.compile_counter.compiles - compiles}))
    restore_both(ctx)
