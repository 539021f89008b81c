"""One run of one cell: load, warm up, measure, check, report.

``run_cell`` is what ``bench/run.py`` calls.  It builds the cell's store
from its configuration with every option passed explicitly, loads the
objects through ``multi_set``, warms up every shape the window will use,
measures for ``seconds`` on the open-loop driver, then compares what the
window produced with the plain reference (``bench/reference.py``) and
returns the result line.  Metrics are read by ``bench/metrics/<name>.py``
from the ``Run`` record, which also carries the window's change in every
counter of the store and of its coding engine (``Run.counters``).
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import sys
import time

import numpy as np

from bench import reference, spec
from bench.driver import CheckedClient, drive
from bench.stream import (GET, SET, UPDATE, key, load_values, make_stream,
                          rng_for)

PALLAS = "pallas-compiled"
TRACE_AT = 0.4        # the traced sub-window opens at this share of the window
TRACE_S = 4.0         # and lasts this long
TRACE_DIR = spec.BENCH / ".traces"
# coding ops that only some kinds of request run: the RS parity delta of
# a sealed object's UPDATE (or upsert); seals fold through
# ``delta_per_item`` and decodes through ``matmul`` whatever the mix
OP_KINDS = {"delta": ("update", "set")}


class BenchFailure(Exception):
    """A refusal or a failed step: the run prints no result."""


class CompileCounter:
    """Backend compiles and persistent-cache loads, from JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.backend = self.hits = self.misses = 0
        self.backend_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend += 1
            self.backend_s += secs

    @property
    def compiles(self) -> int:
        return self.backend + self.hits


class GcPauses:
    """Pauses of Python's cyclic garbage collector, from ``gc.callbacks``,
    inside a ``with`` block."""

    def __init__(self):
        self.pauses: list[tuple[int, float]] = []   # (generation, seconds)
        self._t = None

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._cb)

    def summary(self) -> dict:
        return {"collections": [sum(g == n for g, _ in self.pauses)
                                for n in range(3)],
                "longest_s": max((s for _, s in self.pauses), default=0.0),
                "total_s": sum(s for _, s in self.pauses)}


def host_stalls(run: "Run", slowest: dict, top: int = 3) -> dict:
    """Where the slowest ops waited: the longest service windows (issue
    time, seconds, ops), the slowest call of each kind (seconds, start,
    ops), and the 99th percentile of every op's latency with the spread
    of due times of the ops beyond it (clustered due times: one stall)."""
    w = run.windows
    dur = w[:, 1] - w[:, 0] if len(w) else np.zeros(0)
    longest = [[float(w[i, 0]), float(dur[i]), int(w[i, 2])]
               for i in np.argsort(dur)[::-1][:top]]
    lat = run.latency_s
    slow = {}
    if len(lat) and not np.isnan(lat).any():
        p99 = np.percentile(lat, 99)
        beyond = lat >= p99
        q = np.percentile(run.due[beyond], [0, 25, 50, 75, 100])
        slow = {"p99_ms": 1e3 * float(p99),
                "due_quartiles_s": [float(x) for x in q],
                "wait_to_issue_ms": 1e3 * float(np.mean(
                    run.issue[beyond] - run.due[beyond]))}
    names = {GET: "multi_get", UPDATE: "multi_update", SET: "multi_set"}
    return {"longest_windows": longest,
            "slowest_calls": {names[k]: list(v) for k, v in slowest.items()},
            "beyond_p99": slow}


@dataclasses.dataclass
class Ctx:
    """What a traffic hook sees and sets."""
    cluster: object
    client: CheckedClient
    cfg: dict
    traffic: dict
    required_ops: set
    recover_s: list | None = None
    recovery_snapshots: list = dataclasses.field(default_factory=list)
    failed_server: int | None = None


@dataclasses.dataclass
class Run:
    """The record metric readers read.  Times are seconds from the
    traffic window's start on the driver's clock."""
    setup_s: float
    traffic_s: float
    due: np.ndarray
    issue: np.ndarray
    done: np.ndarray
    windows: np.ndarray        # (t_issue, t_end, ops, call_s, dispatches)
    dispatches_at_open: int
    compiles_in_window: int
    recover_s: list | None
    trace_bounds: tuple | None  # driver-clock span the profiler covered
    trace: object | None        # bench.trace.Reduction of the traced run
    # change over the traffic window of each numeric entry of the store's
    # ``stats`` and ``wall_stats`` and of its engine's ``stats()``
    counters: dict = dataclasses.field(default_factory=dict)

    @property
    def latency_s(self) -> np.ndarray:
        return self.done - self.due


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def cluster_kwargs(cfg: dict) -> dict:
    """Every option of the store, from the configuration, none left to
    a default or an environment variable."""
    return dict(num_servers=cfg["num_servers"], num_proxies=cfg["num_proxies"],
                scheme=cfg["scheme"], n=cfg["n"], k=cfg["k"], c=cfg["c"],
                chunk_size=cfg["chunk_size"], max_unsealed=cfg["max_unsealed"],
                engine=cfg["engine"], shards=cfg["shards"],
                placement=cfg["placement"], async_engine=cfg["async_engine"],
                redundant_reads=cfg["redundant_reads"],
                hot_key_threshold=cfg["hot_key_threshold"])


def counters(cluster) -> dict:
    """Every numeric counter of the store (``stats``, ``wall_stats``) and
    of its coding engine (``stats()``), by name; a name in two of them
    keeps the store's ``stats`` value."""
    out = {}
    for src in (cluster.engine.stats(), cluster.wall_stats, cluster.stats):
        out.update((k, v) for k, v in src.items()
                   if isinstance(v, (int, float)) and not isinstance(v, bool))
    return out


def sealed_data_chunks(cluster) -> int:
    """Sealed data chunks held by all servers (a seal adds one)."""
    return sum(sealed and cid.position < cluster.k
               for s in cluster.servers
               for cid, sealed in zip(s.chunk_ids, s.sealed)
               if cid is not None)


def required_ops(cfg: dict, traffic: dict) -> set:
    """The coding ops a run must show: the configuration's and the
    traffic's, less those that only kinds absent from the mix run."""
    mix = {k for k, share in traffic["mix"].items() if share > 0}
    return {op for op in (set(cfg["required_ops"])
                          | set(traffic.get("required_ops", ())))
            if mix.intersection(OP_KINDS.get(op, mix))}


def load(client: CheckedClient, cfg: dict, seed: int) -> None:
    values = load_values(cfg, rng_for(seed, 0))
    batch, nprox = cfg["load_batch"], cfg["num_proxies"]
    for s in range(0, len(values), batch):
        client.multi_set([(key(i), values[i])
                          for i in range(s, min(s + batch, len(values)))],
                         (s // batch) % nprox)
    if client.refused or len(client.acked) != cfg["objects"]:
        raise BenchFailure(f"load: {len(client.acked)} of {cfg['objects']} "
                           f"objects acknowledged")


def max_updates_per_window(stream, max_window: int) -> int:
    """The most UPDATEs any window of ``stream`` can carry: the batch
    sizes the update path can see."""
    c = np.concatenate([[0], np.cumsum(stream.kind == UPDATE)])
    w = min(max_window, len(stream))
    return int((c[w:] - c[:-w]).max()) if w else 0


def warm_update_shapes(engine, cfg: dict, umax: int) -> None:
    """One batched parity-update call for each batch size 1..umax, the
    shapes a window's ``multi_update`` sends to the coding engine."""
    m, k, C = cfg["n"] - cfg["k"], cfg["k"], cfg["chunk_size"]
    for b in range(1, umax + 1):
        engine.submit_apply_delta(np.zeros((b, m, C), np.uint8),
                                  np.arange(b) % k,
                                  np.zeros((b, C), np.uint8)).result()


class Tracer:
    """Starts the profiler at ``TRACE_AT`` of the window for ``TRACE_S``
    seconds, from the driver's tick between windows.  Each cell keeps
    only its latest trace, under ``TRACE_DIR/<cell>``."""

    def __init__(self, traffic_s: float, tag: str):
        self.on_at = TRACE_AT * traffic_s
        self.dir = TRACE_DIR / tag
        self.bounds: tuple | None = None
        self._t_on = None

    def tick(self, now: float) -> None:
        import jax
        if self.bounds is not None:
            return
        if self._t_on is None and now >= self.on_at:
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self._t_on = now
        elif self._t_on is not None and now >= self._t_on + TRACE_S:
            self.stop(now)

    def stop(self, now: float) -> None:
        import jax
        if self._t_on is not None and self.bounds is None:
            jax.profiler.stop_trace()
            self.bounds = (self._t_on, now)


@dataclasses.dataclass
class Cell:
    """A cell's store, loaded and ready for warm-up."""
    cfg: dict
    traffic: dict
    hook: object | None
    ctx: Ctx
    counter: CompileCounter
    devices: list
    expected_path: str

    @property
    def cluster(self):
        return self.ctx.cluster

    @property
    def client(self) -> CheckedClient:
        return self.ctx.client


def prepare(workload: str, seed: int, *, bench: dict | None = None,
            overrides: dict | None = None,
            traffic_overrides: dict | None = None,
            require_tpu: bool = True, expected_path: str = PALLAS) -> Cell:
    """Refuse what the benchmark must refuse, build the cell's store with
    every option explicit, load it and run the traffic hook's set-up.

    ``overrides`` / ``traffic_overrides`` replace configuration and
    traffic values (tests shrink a cell to the CPU with them);
    ``require_tpu=False`` skips the look for a chip.
    """
    bench = bench or spec.load_benchmark()
    entry = spec.cell(bench, workload)
    cfg = {**spec.config(bench, entry["config"]), **(overrides or {})}
    traffic, hook = spec.traffic(entry["traffic"])
    traffic = {**traffic, **(traffic_overrides or {})}

    import jax
    from repro.core.shard import make_cluster
    from repro.kernels import dispatch
    counter = CompileCounter()
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise BenchFailure(f"no TPU: JAX's default device is {dev.platform}"
                           f" ({dev.device_kind})")
    if len(devices) < entry["chips"]:
        raise BenchFailure(f"cell {workload} needs {entry['chips']} chips, "
                           f"JAX finds {len(devices)}")
    path = dispatch.decide().path
    if path != expected_path:
        raise BenchFailure(f"kernels would run as {path!r}, not "
                           f"{expected_path!r}")

    cluster = make_cluster(**cluster_kwargs(cfg))
    client = CheckedClient(cluster)
    load(client, cfg, seed)
    ctx = Ctx(cluster, client, cfg, traffic, required_ops(cfg, traffic))
    if hook is not None:
        hook.setup(ctx)
    return Cell(cfg, traffic, hook, ctx, counter, devices, expected_path)


def warm_up(cell: Cell, streams: list) -> None:
    """Compile every update batch size ``streams`` can send, drive the
    cell's own traffic from the warm-up seed stream, then hand over to
    the hook's ``after_warmup``."""
    maxw = cell.traffic["max_window_ops"]
    warm_update_shapes(cell.cluster.engine, cell.cfg,
                       max(max_updates_per_window(s, maxw) for s in streams))
    drive(cell.client, streams[0], max_window=maxw,
          num_proxies=cell.cfg["num_proxies"], engine=cell.cluster.engine)
    if cell.hook is not None:
        cell.hook.after_warmup(cell.ctx)
    if cell.client.refused or cell.client.wrong_reads:
        raise BenchFailure(f"warm-up: {cell.client.wrong_reads} wrong reads,"
                           f" {cell.client.refused} refused writes")


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float | None = None, fault: str | None = None,
             **prepare_kw) -> dict:
    """Run one cell; returns the result line as a dict.  ``fault`` arms
    one of ``bench.faults`` at the window's start; ``prepare_kw`` go to
    ``prepare``."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = prepare_kw.pop("bench", None) or spec.load_benchmark()
    cell = prepare(workload, seed, bench=bench, **prepare_kw)
    cfg, traffic, hook, ctx = cell.cfg, cell.traffic, cell.hook, cell.ctx
    cluster, client, counter = cell.cluster, cell.client, cell.counter
    engine = cluster.engine
    dev = cell.devices[0]

    rate, maxw = traffic["rate_ops_per_s"], traffic["max_window_ops"]
    traffic_s = seconds - traffic.get("window_reserve_s", 0.0)
    if traffic_s <= 0:
        raise BenchFailure(f"--seconds {seconds} leaves no traffic window")
    warm = make_stream(cfg, traffic, rate, traffic["warmup_s"],
                       rng_for(seed, 1))
    # the window's inserts take the ids after the warm-up's
    stream = make_stream(cfg, traffic, rate, traffic_s, rng_for(seed, 2),
                         first_insert_id=cfg["objects"] + warm.inserts)
    warm_up(cell, [warm, stream])
    # every window starts from a fresh full collection, not from wherever
    # the load left the collector's generation counts
    gc.collect()
    compiles0 = counter.compiles

    t_window = time.perf_counter()
    setup_s = t_window - t_start
    if fault is not None:
        from bench import faults
        faults.arm(fault, cluster)
    if hook is not None:
        hook.open_window(ctx)
    tracer = Tracer(traffic_s, workload) if trace else None
    counters0, sealed0 = counters(cluster), sealed_data_chunks(cluster)
    dispatches0 = engine.device_dispatches
    t_traffic = time.perf_counter()
    with GcPauses() as gc_pauses:
        rec = drive(client, stream, max_window=maxw,
                    num_proxies=cfg["num_proxies"], engine=engine,
                    t0=t_traffic, tick=tracer.tick if tracer else None)
    counters1, sealed1 = counters(cluster), sealed_data_chunks(cluster)
    if tracer is not None:
        tracer.stop(time.perf_counter() - t_traffic)
    window_s = time.perf_counter() - t_window
    compiles = counter.compiles - compiles0
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    wrong_in_window, refused = client.wrong_reads, client.refused
    undone = int(np.isnan(rec.done).sum())

    # the reference, once the window has closed
    written = sorted({key(int(i)) for i in stream.ids[stream.kind != GET]})
    reference.read_back(client, written, cfg["num_proxies"])
    if hook is not None:
        hook.close_window(ctx)
    stripes_checked, bad_stripes = reference.check_parity(cluster, cfg)
    chunks_checked, bad_chunks = reference.check_recovered(
        ctx.recovery_snapshots)
    off_path, missing = reference.check_paths(engine.op_paths,
                                              cell.expected_path,
                                              ctx.required_ops)
    checks = {
        "wrong_reads": client.wrong_reads,
        "refused_writes": client.refused,
        "unanswered_ops": undone,
        "bad_parity_stripes": bad_stripes,
        "no_stripes_checked": int(stripes_checked == 0),
        "bad_rebuilt_chunks": bad_chunks,
        "ops_off_path": off_path,
        "ops_never_run": missing,
    }
    if not ctx.recovery_snapshots:
        del checks["bad_rebuilt_chunks"]
    correct = all(v <= 0 for v in checks.values())

    reduction = None
    if tracer is not None and tracer.bounds is not None:
        from bench import trace as trace_mod
        reduction = trace_mod.reduce_dir(tracer.dir)
    run = Run(setup_s=setup_s, traffic_s=traffic_s, due=stream.due,
              issue=rec.issue, done=rec.done,
              windows=np.array(rec.windows, float).reshape(-1, 5),
              dispatches_at_open=dispatches0, compiles_in_window=compiles,
              recover_s=ctx.recover_s,
              trace_bounds=tracer.bounds if tracer else None,
              trace=reduction,
              counters={k: v - counters0.get(k, 0)
                        for k, v in counters1.items()})
    metrics = {}
    for m in spec.metrics_for(bench, workload, trace):
        v = spec.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(cell.devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(stream),
              "failed": wrong_in_window + refused + undone,
              "metrics": metrics, "device": device}
    if reduction is not None:
        if reduction.busy_s is not None:
            device["busy_s"] = reduction.busy_s
            device["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
    log(json.dumps({"window_s": window_s, "windows": len(rec.windows),
                    "stripes_checked": stripes_checked,
                    "sealed_data_chunks": [sealed0, sealed1],
                    "counters": {k: v for k, v in run.counters.items() if v},
                    "chunks_checked": chunks_checked,
                    "reads_checked": client.reads,
                    "backlog_at_close": int(np.sum(
                        np.nan_to_num(rec.issue, nan=np.inf) > traffic_s)),
                    "compile_cache": {"hits": counter.hits,
                                      "misses": counter.misses,
                                      "backend_compile_s": counter.backend_s},
                    "gc_in_window": gc_pauses.summary(),
                    "stalls": host_stalls(run, rec.slowest),
                    "op_paths": engine.op_paths}))
    result["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    for k, v in checks.items():
        log(f"check {k} = {v} (limit 0)")
    return result
