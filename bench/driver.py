"""The client side: a checked multi-key client and the open-loop driver.

The driver issues ops at their due times whatever the store's speed.
Each pass gathers every op that is due, up to ``max_window`` of them, and
sends them as one window of per-kind ``multi_get`` / ``multi_update`` /
``multi_set`` calls through one proxy, proxies taken round-robin.  A
window ends early at an op whose key the window already holds under
another kind, so reads and writes of one key keep their order.  An op's
latency runs from its due time to the return of the call that carries it.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench.stream import GET, SET, UPDATE, Stream, key

CALL_SPANS = {GET: "bench.multi_get", UPDATE: "bench.multi_update",
              SET: "bench.multi_set"}
WAIT_SPAN = "bench.wait"
GATHER_SPAN = "bench.gather"


class CheckedClient:
    """The multi-key API of a cluster, holding every answer to a dict of
    the writes the cluster acknowledged (the reference for reads)."""

    def __init__(self, cluster):
        self.cluster = cluster
        self.acked: dict[bytes, bytes] = {}
        self.reads = self.wrong_reads = 0
        self.writes = self.refused = 0

    def multi_set(self, items, proxy_id):
        return self._writes(items, self.cluster.multi_set(items,
                                                          proxy_id=proxy_id))

    def multi_update(self, items, proxy_id):
        return self._writes(items, self.cluster.multi_update(
            items, proxy_id=proxy_id))

    def _writes(self, items, ok):
        for (k, v), acked in zip(items, ok):
            self.writes += 1
            if acked:
                self.acked[k] = v
            else:
                self.refused += 1
        return ok

    def multi_get(self, keys, proxy_id):
        values = self.cluster.multi_get(keys, proxy_id=proxy_id)
        for k, v in zip(keys, values):
            self.reads += 1
            if v != self.acked.get(k):
                self.wrong_reads += 1
        return values


class Recorder:
    """Per-op and per-window times of one driven stream, in seconds from
    the stream's start on the driver's clock (``time.perf_counter``)."""

    def __init__(self, n: int):
        self.issue = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.windows: list[tuple] = []   # (t_issue, t_end, ops, call_s, dispatches)
        # per kind, its slowest call: (seconds, t_start, ops)
        self.slowest: dict[int, tuple] = {}


def drive(client: CheckedClient, stream: Stream, *, max_window: int,
          num_proxies: int, engine, t0: float | None = None,
          deadline_s: float = 60.0, tick=None) -> Recorder:
    """Serve ``stream`` open-loop from ``t0`` (default: now).

    Ops still unserved ``deadline_s`` after the last one was due are left
    undone (their ``done`` stays NaN).  ``tick(now)``, when given, runs
    between windows (the traced run starts and stops the profiler there).
    """
    clock = time.perf_counter
    t0 = clock() if t0 is None else t0
    rec = Recorder(len(stream))
    due, kinds, ids, values = stream.due, stream.kind, stream.ids, stream.values
    n = len(stream)
    stop_at = (due[-1] if n else 0.0) + deadline_s
    i = w = 0
    while i < n:
        now = clock() - t0
        if tick is not None:
            tick(now)
        if due[i] > now:
            with TraceAnnotation(WAIT_SPAN):
                time.sleep(due[i] - now)
            continue
        if now > stop_at:
            break
        with TraceAnnotation(GATHER_SPAN):
            j = i
            held: dict[int, int] = {}
            while j < n and j - i < max_window and due[j] <= now:
                prev = held.setdefault(int(ids[j]), int(kinds[j]))
                if prev != kinds[j]:
                    break
                j += 1
            groups: dict[int, list[int]] = {}
            for t in range(i, j):   # kinds keep first-arrival order
                groups.setdefault(int(kinds[t]), []).append(t)
        rec.issue[i:j] = now
        pid = w % num_proxies
        call_s = 0.0
        for kind, idx in groups.items():
            c0 = clock()
            with TraceAnnotation(CALL_SPANS[kind], ops=len(idx)):
                if kind == GET:
                    client.multi_get([key(int(ids[t])) for t in idx], pid)
                elif kind == UPDATE:
                    client.multi_update([(key(int(ids[t])), values[t])
                                         for t in idx], pid)
                else:
                    client.multi_set([(key(int(ids[t])), values[t])
                                      for t in idx], pid)
            c1 = clock()
            call_s += c1 - c0
            if c1 - c0 > rec.slowest.get(kind, (0.0,))[0]:
                rec.slowest[kind] = (c1 - c0, c0 - t0, len(idx))
            rec.done[idx] = c1 - t0
        rec.windows.append((now, clock() - t0, j - i, call_s,
                            engine.device_dispatches))
        i = j
        w += 1
    return rec
