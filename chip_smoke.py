#!/usr/bin/env python3
"""Chip smoke test: the MemEC store's served path on one TPU, end to end.

    python chip_smoke.py                  # RS(10,8) then RDP(10,8), one chip
    python chip_smoke.py --objects N      # load fewer objects (prints the cut)
    python chip_smoke.py --four-chips     # ECStateStore on a (4, 1) mesh only

Each scheme runs the paper's §7 testbed (16 servers, 4 proxies, c=16,
4 KB chunks, 24-byte keys, 8/32-byte values, Zipf 0.99) through the
normal entry points with ``engine="pallas"``:

1. load: 1,000,000 objects through ``multi_set`` in batches of 256;
2. normal mode: 20,000 YCSB-A ops (GET + batched UPDATE) and 20,000
   YCSB-C ops, batch 256;
3. degraded mode: fail the server holding the most sealed chunks (one
   batched recovery decode), read 10,000 of its keys, restore it, read
   them again;
4. checks: every answered read equals a dict of acknowledged writes, a
   sampled parity check decodes through the numpy codes with 0 bad, and
   every coding op ran as compiled Pallas.

Earlier lines report each phase as JSON; the last line is exactly
``{"ok": true, "device": {...}}``.  The script exits non-zero, printing
no result, when JAX finds no TPU, when ``$MEMEC_INTERPRET`` is set, or
when any phase or check fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PAPER_OBJECTS = 1_000_000
LOAD_BATCH = 256
YCSB_OPS = 20_000
DEGRADED_READS = 10_000
PARITY_SAMPLE = 2_000
FOUR_CHIP_PAGES_MIB = 256

# the dispatch path every coding op must have taken on the chip
EXPECTED_PATH = "pallas-compiled"


class SmokeFailure(Exception):
    pass


def fail(msg: str) -> None:
    raise SmokeFailure(msg)


def emit(**fields) -> None:
    print(json.dumps(fields, sort_keys=True, default=str), flush=True)


class CompileCounter:
    """Persistent-cache hits/misses and backend compile seconds, read
    from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def snapshot(self) -> dict:
        return {"cache_hits": self.hits, "cache_misses": self.misses,
                "backend_compile_s": self.compile_s}


class CheckedClient:
    """The multi-key client API in front of a cluster, holding every read
    it answers to a dict of the writes the cluster acknowledged."""

    def __init__(self, cluster):
        self.cluster = cluster
        self.num_proxies = cluster.num_proxies
        self.async_engine = cluster.async_engine
        self.acked: dict[bytes, bytes] = {}
        self.reads = self.writes = 0
        self.mismatches = self.refused = 0

    def _writes(self, items, ok):
        for (key, value), acked in zip(items, ok):
            self.writes += 1
            if acked:
                self.acked[key] = value
            else:
                self.refused += 1

    def multi_set(self, items, proxy_id=0):
        ok = self.cluster.multi_set(items, proxy_id=proxy_id)
        self._writes(items, ok)
        return ok

    def multi_update(self, items, proxy_id=0):
        ok = self.cluster.multi_update(items, proxy_id=proxy_id)
        self._writes(items, ok)
        return ok

    def multi_get(self, keys, proxy_id=0):
        values = self.cluster.multi_get(keys, proxy_id=proxy_id)
        for key, value in zip(keys, values):
            self.reads += 1
            if value != self.acked.get(key):
                self.mismatches += 1
        return values

    def check(self, phase: str) -> None:
        if self.mismatches or self.refused:
            fail(f"{phase}: {self.mismatches} reads differ from the "
                 f"acknowledged writes, {self.refused} writes refused")


def run_store(scheme: str, objects: int, counter: CompileCounter, *,
              ops: int = YCSB_OPS, degraded_reads: int = DEGRADED_READS,
              parity_sample: int = PARITY_SAMPLE, seed: int = 42) -> None:
    """Load, serve, fail, recover and check one scheme's cluster."""
    import numpy as np
    from repro.configs.memec import CONFIG, make_configured_cluster
    from repro.core.invariants import parity_invariant
    from repro.data.ycsb import YCSBConfig, run_workload

    cluster = make_configured_cluster(CONFIG, scheme=scheme, engine="pallas")
    engine = cluster.engine
    client = CheckedClient(cluster)
    cfg = YCSBConfig(num_objects=objects, key_size=CONFIG.key_size,
                     value_sizes=CONFIG.value_sizes, zipf_theta=0.99,
                     seed=seed)
    code = f"{scheme.upper()}({cluster.n},{cluster.k})"

    def phase(name, fn, **extra):
        d0 = engine.device_dispatches
        t0 = time.perf_counter()
        n_ops = fn()
        wall = time.perf_counter() - t0
        client.check(f"{code} {name}")
        emit(phase=name, scheme=code, ops=n_ops, wall_s=wall,
             device_dispatches=engine.device_dispatches - d0,
             op_paths=dict(engine.op_paths), compile=counter.snapshot(),
             **extra)

    phase("load", lambda: run_workload(client, "load", objects, cfg,
                                       batch_size=LOAD_BATCH)[0],
          objects=objects)
    if len(client.acked) != objects:
        fail(f"{code} load: {len(client.acked)} of {objects} acknowledged")
    phase("ycsb-A", lambda: run_workload(client, "A", ops, cfg,
                                         batch_size=LOAD_BATCH)[0])
    phase("ycsb-C", lambda: run_workload(client, "C", ops, cfg,
                                         batch_size=LOAD_BATCH)[0])

    # degraded mode: the server holding the most sealed chunks fails
    sealed = [sum(s.sealed) for s in cluster.servers]
    sid = int(np.argmax(sealed))
    keys = sorted(cluster.servers[sid].object_index.keys())
    pick = np.random.default_rng(seed).choice(
        len(keys), min(degraded_reads, len(keys)), replace=False)
    keys = [keys[i] for i in sorted(pick)]

    def read_keys():
        for i in range(0, len(keys), LOAD_BATCH):
            client.multi_get(keys[i:i + LOAD_BATCH])
        return len(keys)

    stats0 = cluster.stats["degraded_requests"]
    d0 = engine.device_dispatches
    t0 = time.perf_counter()
    timings = cluster.fail_server(sid)
    emit(phase="fail-server", scheme=code, failed_server=sid,
         sealed_chunks_on_server=sealed[sid],
         recovered_chunks=timings["recovered_chunks"],
         wall_s=time.perf_counter() - t0,
         device_dispatches=engine.device_dispatches - d0,
         op_paths=dict(engine.op_paths), compile=counter.snapshot())
    if timings["recovered_chunks"] != sealed[sid]:
        fail(f"{code} fail-server: recovered {timings['recovered_chunks']} "
             f"of {sealed[sid]} sealed chunks")
    phase("degraded-reads", read_keys, failed_server=sid)
    if cluster.stats["degraded_requests"] - stats0 < len(keys):
        fail(f"{code} degraded: reads did not take the degraded path")
    phase("restored", lambda: (cluster.restore_server(sid), read_keys())[1],
          restored_server=sid)

    t0 = time.perf_counter()
    checked, bad = parity_invariant(cluster, sample=parity_sample, seed=seed)
    emit(phase="parity-check", scheme=code, stripes_checked=checked, bad=bad,
         wall_s=time.perf_counter() - t0, reads_checked=client.reads,
         writes_acked=client.writes, oracle_mismatches=client.mismatches)
    if bad or not checked:
        fail(f"{code} parity check: {bad} bad of {checked}")

    check_op_paths(code, scheme, engine.op_paths)


def check_op_paths(code: str, scheme: str, op_paths: dict) -> None:
    """Every coding op ran on ``EXPECTED_PATH``, and the ops the served
    path needs all ran: decode (``matmul``) and the seal fold / RDP
    deltas (``delta_per_item``), plus RS's batched UPDATE (``delta``)."""
    required = {"matmul", "delta_per_item"} | ({"delta"} if scheme == "rs"
                                               else set())
    missing = required - set(op_paths)
    if missing:
        fail(f"{code}: ops never exercised: {sorted(missing)}")
    off = {op: p for op, p in op_paths.items() if p != EXPECTED_PATH}
    if off:
        fail(f"{code}: ops not on {EXPECTED_PATH}: {off}")


def run_four_chips(pages_mib: int = FOUR_CHIP_PAGES_MIB,
                   seed: int = 0) -> None:
    """ECStateStore on a (4, 1) data mesh: encode, one parity delta and
    one lost device's reconstruction, each byte-compared with RSCode."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.ecstore import ECConfig, ECStateStore
    from repro.launch.mesh import make_mesh

    A = 4
    if len(jax.devices()) < A:
        fail(f"--four-chips needs {A} devices, found {len(jax.devices())}")
    # a device holds at most one member of each stripe, so n <= A
    cfg = ECConfig(k=2, m=2, page_size=4096)
    code = cfg.code
    pages = pages_mib * 2**20 // cfg.page_size
    S = pages // cfg.k
    mesh = make_mesh((A, 1), ("data", "model"))
    spec = P("data", "model", None, None)
    sharded = NamedSharding(mesh, spec)
    store = ECStateStore(mesh, spec, cfg)
    emit(phase="four-chip-setup", code=f"RS({code.n},{code.k})",
         mesh=dict(mesh.shape), pages_per_device=pages,
         mib_per_device=pages * cfg.page_size / 2**20)

    shape = (A, 1, pages, cfg.page_size)
    make_state = jax.jit(
        lambda key: jax.random.bits(key, shape, jnp.uint8),
        out_shardings=sharded)
    # xor fresh bytes into every 8th page of every device
    mutate = jax.jit(
        lambda st, key: st ^ jnp.where(
            (jnp.arange(pages) % 8 == 0)[None, None, :, None],
            jax.random.bits(key, shape, jnp.uint8), jnp.uint8(0)),
        out_shardings=sharded)

    def on_four(name, arr):
        devs = {s.device for s in arr.addressable_shards}
        rows = {s.index[0].start for s in arr.addressable_shards}
        if len(devs) != A or len(rows) != A:
            fail(f"{name}: shards on {len(devs)} devices, "
                 f"{len(rows)} distinct rows")
        return sorted(d.id for d in devs)

    def oracle_parity(state: np.ndarray) -> np.ndarray:
        """RSCode parity of every rotational stripe list, laid out like
        ECStateStore's (A, 1, m, S, page) buffer."""
        out = np.zeros((A, 1, cfg.m, S, cfg.page_size), np.uint8)
        step = 4096
        for l in range(A):
            for s0 in range(0, S, step):
                s1 = min(S, s0 + step)
                data = np.stack([state[(l + j) % A, 0,
                                       s0 * cfg.k + j: s1 * cfg.k: cfg.k]
                                 for j in range(cfg.k)])   # (k, s, page)
                par = code.encode(data.reshape(cfg.k, -1))
                for r in range(cfg.m):
                    out[(l + cfg.k + r) % A, 0, r, s0:s1] = \
                        par[r].reshape(s1 - s0, cfg.page_size)
        return out

    def timed(fn):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        return out, time.perf_counter() - t0

    key0, key1 = jax.random.split(jax.random.key(seed))
    state, _ = timed(lambda: make_state(key0))
    parity, t_enc = timed(lambda: store.encode(state))
    host_state = np.asarray(state)
    if not np.array_equal(np.asarray(parity), oracle_parity(host_state)):
        fail("four-chip encode differs from RSCode")
    emit(phase="four-chip-encode", wall_s=t_enc, byte_equal=True,
         devices=on_four("parity", parity), state_devices=on_four("state",
                                                                   state))

    new_state = mutate(state, key1)
    parity2, t_delta = timed(
        lambda: store.delta_update(state, new_state, parity))
    host_new = np.asarray(new_state)
    if not np.array_equal(np.asarray(parity2), oracle_parity(host_new)):
        fail("four-chip parity delta differs from RSCode")
    emit(phase="four-chip-delta", wall_s=t_delta, byte_equal=True,
         devices=on_four("parity2", parity2),
         pages_changed=int((host_new != host_state).any(-1).sum()))

    failed = 1
    lose = jax.jit(lambda x: x.at[failed].set(0), out_shardings=sharded)
    lose_par = jax.jit(lambda x: x.at[failed].set(0),
                       out_shardings=NamedSharding(
                           mesh, P("data", "model", None, None, None)))
    holed, holed_par = lose(new_state), lose_par(parity2)
    rec, t_rec = timed(lambda: store.reconstruct(holed, holed_par, failed))
    host_rec = np.asarray(rec)
    for d in range(A):
        if not np.array_equal(host_rec[d, 0], host_new[failed, 0]):
            fail(f"four-chip reconstruction on device {d} differs from the "
                 f"lost device's pages")
    emit(phase="four-chip-reconstruct", wall_s=t_rec, byte_equal=True,
         failed_device=failed, devices=on_four("reconstruction", rec))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--objects", type=int, default=PAPER_OBJECTS,
                    help="objects loaded per scheme (default: the "
                         "paper-scale 1,000,000)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip ECStateStore phase")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"repository sources not found under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    if os.environ.get("MEMEC_INTERPRET", "").strip():
        fail("MEMEC_INTERPRET is set: the kernels would run in interpret "
             "mode, not compiled for the chip")

    import jax
    from repro.kernels import dispatch
    cache_dir = dispatch.enable_compile_cache()
    counter = CompileCounter()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's default device is {dev.platform} "
             f"({dev.device_kind})")
    emit(phase="start", platform=dev.platform, device_kind=dev.device_kind,
         device_count=len(devices), compile_cache_dir=cache_dir,
         jax_version=jax.__version__)

    t0 = time.perf_counter()
    if args.four_chips:
        run_four_chips()
    else:
        if args.objects < PAPER_OBJECTS:
            emit(phase="cut", objects=args.objects,
                 paper_objects=PAPER_OBJECTS)
        for scheme in ("rs", "rdp"):
            run_store(scheme, args.objects, counter)
    emit(phase="done", wall_s=time.perf_counter() - t0,
         compile=counter.snapshot())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
