"""Reduce a profiler trace of the window to device busy time, coding
kernel time and idle gaps named by what the host was doing.

Read from a JAX profiler ``.xplane.pb`` with ``jax.profiler.ProfileData``:

- the traced window runs from the first to the last benchmark span
  (``bench.*`` ``TraceAnnotation``s of the driver) on the host plane;
- busy time is the union of the executions on each device plane's
  ``XLA Modules`` line inside the window, averaged over the devices that
  ran anything;
- coding kernel time is the summed duration of the ``XLA Ops`` events
  that are Pallas kernels (``tpu_custom_call``) inside the window: every
  Pallas kernel of this store is a coding kernel;
- each idle gap between executions is charged to the benchmark span the
  host was in, in proportion to their overlap.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
MODULE_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
SPAN_PREFIX = "bench."
CALL_PREFIX = "bench.multi_"
OUTSIDE = "outside benchmark spans"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float | None      # None: the trace holds no device plane
    kernel_s: float
    ops: int                  # ops carried by the traced calls
    device_ops: list          # [name, seconds], most time first
    idle_gaps: list           # [host span, idle seconds], most first

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops[:10],
                "idle_gaps": self.idle_gaps[:10]}


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, w0: float, w1: float) -> tuple[float, float]:
    return max(s, w0), min(e, w1)


def op_name(module: str, op: str) -> str:
    """``jit_f(123)`` and ``%f.1 = u8[...] ...`` -> ``jit_f/f``."""
    mod = module.split("(")[0]
    m = re.match(r"%?([\w\-]+?)(\.\d+)?\s*=", op)
    return f"{mod}/{m.group(1) if m else op.split(' ')[0]}"


def reduce_profile(pd) -> Reduction | None:
    spans = []     # (start, end, name, ops)
    devices = []   # (module events, op events) per device plane
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        ops = dict(e.stats).get("ops", 0) if e.stats else 0
                        spans.append((e.start_ns, e.end_ns, e.name, int(ops)))
        elif plane.name.startswith("/device:"):
            lines = {l.name: l for l in plane.lines}
            if MODULE_LINE in lines:
                devices.append((list(lines[MODULE_LINE].events),
                                list(lines[OPS_LINE].events)
                                if OPS_LINE in lines else []))
    if not spans:
        return None
    w0 = min(s for s, _, _, _ in spans)
    w1 = max(e for _, e, _, _ in spans)
    ops = sum(n for s, e, name, n in spans if name.startswith(CALL_PREFIX))
    busy_ns, kernel_ns = [], 0.0
    per_op: dict[str, float] = {}
    idle: dict[str, float] = {}
    used = 0
    for modules, op_events in devices:
        ivs = [_clip(e.start_ns, e.end_ns, w0, w1) for e in modules]
        ivs = _union([(s, e) for s, e in ivs if e > s])
        if not ivs:
            continue
        used += 1
        busy_ns.append(sum(e - s for s, e in ivs))
        # modules hold their ops: name each op by its enclosing module
        mods = sorted((e.start_ns, e.end_ns, e.name) for e in modules)
        j = 0
        for e in sorted(op_events, key=lambda e: e.start_ns):
            if not (w0 <= e.start_ns < w1):
                continue
            while j + 1 < len(mods) and mods[j + 1][0] <= e.start_ns:
                j += 1
            name = op_name(mods[j][2] if mods else "", e.name)
            per_op[name] = per_op.get(name, 0.0) + e.duration_ns
            if KERNEL_MARK in e.name:
                kernel_ns += e.duration_ns
        gaps, t = [], w0
        for s, e in ivs:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < w1:
            gaps.append((t, w1))
        for g0, g1 in gaps:
            covered = 0.0
            for s, e, name, _ in spans:
                o = min(e, g1) - max(s, g0)
                if o > 0:
                    idle[name] = idle.get(name, 0.0) + o
                    covered += o
            if g1 - g0 > covered:
                idle[OUTSIDE] = idle.get(OUTSIDE, 0.0) + (g1 - g0 - covered)
    n = max(used, 1)

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])]
    return Reduction(window_s=(w1 - w0) / 1e9,
                     busy_s=sum(busy_ns) / n / 1e9 if devices else None,
                     kernel_s=kernel_ns / n / 1e9, ops=ops,
                     device_ops=ranked(per_op), idle_gaps=ranked(idle))


def reduce_file(path: Path) -> Reduction | None:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)))


def reduce_dir(directory: Path) -> Reduction | None:
    """The newest ``.xplane.pb`` under ``directory``."""
    files = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return reduce_file(files[-1]) if files else None
