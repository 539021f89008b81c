"""Reduce the program's own spans (``memec.*``, ``repro/core/spans.py``)
in a traced run's profiler trace.

``bench/trace.py`` reduces the benchmark's spans (``bench.*``); this
reads, from the same ``.xplane.pb`` and over the same traced window (the
first to the last ``bench.*`` span), the spans the store, the coding
engine and the kernel front doors record:

- each span name's self time: its time inside the window minus what the
  spans nested in it cover;
- the ``bytes`` each span name carries, summed (host->device on
  ``memec.kernel.stage``, device->host on ``memec.engine.fetch``);
- ``program_idle_gaps``: each device idle gap charged to the innermost
  span the host had open during it, ``memec.*`` first, else the
  ``bench.*`` span as ``bench/trace.py`` charges it, else to time outside
  every span.

A trace holding no ``memec.*`` span (a store without them) reduces to
empty tables, and each reader of ``bench/metrics/`` then returns None.

    python -m bench.spans bench/.traces/<cell>

prints the newest trace's reduction there.
"""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

PROGRAM = "memec."
BENCH = "bench."
CALL_PREFIX = "bench.multi_"
MODULE_LINE = "XLA Modules"
OUTSIDE = "outside benchmark spans"


@dataclasses.dataclass
class Spans:
    window_s: float
    ops: int                  # ops carried by the traced calls
    self_s: dict              # span name -> seconds of self time
    calls: dict               # span name -> calls started in the window
    nbytes: dict              # span name -> summed ``bytes``
    program_idle_gaps: list   # [innermost span, idle seconds], most first

    def total_self_s(self, name: str) -> float | None:
        """Self seconds of ``name`` and of the spans named under it
        (``memec.store`` covers ``memec.store.*``); None when none ran."""
        hit = [v for k, v in self.self_s.items() if _under(k, name)]
        return sum(hit) if hit else None

    def total_bytes(self, name: str) -> int | None:
        hit = [v for k, v in self.nbytes.items() if _under(k, name)]
        return sum(hit) if hit else None


def _under(span: str, name: str) -> bool:
    return span == name or span.startswith(name + ".")


def _segments(events: list[tuple]) -> list[tuple]:
    """Properly nested (start, end, name) events of one host line ->
    disjoint (start, end, name) pieces, each named by the innermost event
    open over it."""
    out: list[tuple] = []
    stack: list[tuple] = []       # (end, name)
    t = 0.0

    def close_until(x: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close_until(s)
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        stack.append((e, name))
        t = s
    close_until(float("inf"))
    return out


def _device_gaps(modules, w0: float, w1: float) -> list[tuple] | None:
    """Idle (start, end) gaps of one device's ``XLA Modules`` line inside
    the window; None when the device ran nothing there."""
    ivs = sorted((max(e.start_ns, w0), min(e.end_ns, w1)) for e in modules)
    ivs = [(s, e) for s, e in ivs if e > s]
    if not ivs:
        return None
    gaps, t = [], w0
    for s, e in ivs:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < w1:
        gaps.append((t, w1))
    return gaps


def reduce_profile(pd) -> Spans | None:
    lines = []            # per host line: (start, end, name) of its spans
    window = []           # bench.* spans: (start, end)
    ops = 0
    calls: dict[str, int] = {}
    nbytes: dict[str, int] = {}
    starts = []           # (start, name, bytes) of memec.* spans
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = []
                for e in line.events:
                    if e.name.startswith(BENCH):
                        window.append((e.start_ns, e.end_ns))
                        if e.name.startswith(CALL_PREFIX):
                            ops += int(dict(e.stats or ()).get("ops", 0))
                    elif e.name.startswith(PROGRAM):
                        starts.append((e.start_ns, e.name, int(
                            dict(e.stats or ()).get("bytes", 0))))
                    else:
                        continue
                    evs.append((e.start_ns, e.end_ns, e.name))
                if evs:
                    lines.append(evs)
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    devices.append(list(line.events))
    if not window:
        return None
    w0, w1 = min(s for s, _ in window), max(e for _, e in window)
    for s, name, b in starts:
        if w0 <= s < w1:
            calls[name] = calls.get(name, 0) + 1
            if b:
                nbytes[name] = nbytes.get(name, 0) + b
    self_ns: dict[str, float] = {}
    segs = []
    for evs in lines:
        for s, e, name in _segments(evs):
            s, e = max(s, w0), min(e, w1)
            if e > s:
                segs.append((s, e, name))
                if name.startswith(PROGRAM):
                    self_ns[name] = self_ns.get(name, 0.0) + (e - s)
    segs.sort()
    idle: dict[str, float] = {}
    used = 0
    for modules in devices:
        gaps = _device_gaps(modules, w0, w1)
        if gaps is None:
            continue
        used += 1
        j = 0
        for g0, g1 in gaps:
            while j < len(segs) and segs[j][1] <= g0:
                j += 1
            covered = 0.0
            i = j
            while i < len(segs) and segs[i][0] < g1:
                s, e, name = segs[i]
                o = min(e, g1) - max(s, g0)
                if o > 0:
                    idle[name] = idle.get(name, 0.0) + o
                    covered += o
                i += 1
            if g1 - g0 > covered:
                idle[OUTSIDE] = idle.get(OUTSIDE, 0.0) + (g1 - g0 - covered)
    n = max(used, 1)
    return Spans(window_s=(w1 - w0) / 1e9, ops=ops,
                 self_s={k: v / 1e9 for k, v in self_ns.items()},
                 calls=calls, nbytes=nbytes,
                 program_idle_gaps=[[k, v / n / 1e9] for k, v in sorted(
                     idle.items(), key=lambda kv: -kv[1])])


def reduce_file(path: Path) -> Spans | None:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)))


def newest_traces(directory: Path) -> list[Path]:
    return sorted(Path(directory).rglob("*.xplane.pb"),
                  key=lambda p: p.stat().st_mtime, reverse=True)


_found: list = [None, None]     # [run.trace, its Spans]


def of_run(run) -> Spans | None:
    """The program spans of a traced run: the newest trace under the
    benchmark's trace directory whose window is the run's own."""
    if run.trace is None:
        return None
    if _found[0] is run.trace:
        return _found[1]
    from bench.harness import TRACE_DIR
    found = None
    for path in newest_traces(TRACE_DIR):
        r = reduce_file(path)
        if r is not None and abs(r.window_s - run.trace.window_s) < 1e-9:
            found = r
            break
    _found[:] = [run.trace, found]
    return found


def self_ms_per_op(run, name: str) -> float | None:
    """Self time of span ``name`` (and those under it) per traced op."""
    s = of_run(run)
    v = s.total_self_s(name) if s is not None else None
    return None if v is None or not run.trace.ops else \
        1e3 * v / run.trace.ops


def bytes_per_op(run, name: str) -> float | None:
    """Summed ``bytes`` of span ``name`` per traced op."""
    s = of_run(run)
    v = s.total_bytes(name) if s is not None else None
    return None if v is None or not run.trace.ops else v / run.trace.ops


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = Path(args[0])
    files = [path] if path.is_file() else newest_traces(path)[:1]
    r = reduce_file(files[0]) if files else None
    if r is None:
        print(f"no traced window under {path}", file=sys.stderr)
        return 1
    per_op = max(r.ops, 1)
    print(json.dumps({
        "trace": str(files[0]), "window_s": r.window_s, "ops": r.ops,
        "self_ms_per_op": {k: 1e3 * v / per_op for k, v in sorted(
            r.self_s.items(), key=lambda kv: -kv[1])},
        "all_program_self_ms_per_op": 1e3 * sum(r.self_s.values()) / per_op,
        "calls": r.calls,
        "bytes_per_op": {k: v / per_op for k, v in r.nbytes.items()},
        "program_idle_gaps": r.program_idle_gaps}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
