"""Wall-clock spans and byte counters inside the program (core/spans.py):
the engine's host<->device byte counters, the recovery split, and a
real profiler trace of the served UPDATE path reduced by the
benchmark's readers."""
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core import MemECCluster, spans
from repro.core.codes import make_code
from repro.core.engine import PallasEngine

B, C = 3, 64


def _apply_delta(scheme):
    eng = PallasEngine(make_code(scheme, 10, 8))
    rng = np.random.default_rng(1)
    parity = rng.integers(0, 256, (B, 2, C), dtype=np.uint8)
    xors = rng.integers(0, 256, (B, C), dtype=np.uint8)
    eng.submit_apply_delta(parity, [0, 3, 7], xors).result()
    return eng


def test_bytes_of_one_rs_update_batch():
    eng = _apply_delta("rs")
    # parity (B, 2, C) + xors (B, C) in, gammas (B, 2) as int32
    assert eng.h2d_bytes == B * 2 * C + B * C + B * 2 * 4
    assert eng.d2h_bytes == B * 2 * C
    assert eng.stats()["h2d_bytes"] == eng.h2d_bytes
    assert eng.stats()["d2h_bytes"] == eng.d2h_bytes


def test_bytes_of_one_rdp_update_batch():
    eng = _apply_delta("rdp")
    r = 16                      # p = 17: a chunk is 16 sub-blocks
    # parity and xors as for RS, plus one (2r, r) uint8 matrix per item
    assert eng.h2d_bytes == B * 2 * C + B * C + B * 2 * r * r
    assert eng.d2h_bytes == B * 2 * C


def test_bytes_of_one_decode():
    eng = PallasEngine(make_code("rs", 10, 8))
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, (B, 8, C), dtype=np.uint8)
    parity = eng.encode_batch(data)
    h0, d0 = eng.h2d_bytes, eng.d2h_bytes
    available = []
    for d, p in zip(data, parity):
        chunks = {i: d[i] for i in range(8)}
        chunks.update({8: p[0], 9: p[1]})
        del chunks[2]                       # one erasure pattern
        available.append(chunks)
    out = eng.submit_decode(available, [[2]] * B, C).result()
    assert all(np.array_equal(o[2], d[2]) for o, d in zip(out, data))
    # one pattern group: the (B, k, C) stack and the (k, k) inverse in,
    # the (B, k, C) decoded rows out
    assert eng.h2d_bytes - h0 == B * 8 * C + 8 * 8
    assert eng.d2h_bytes - d0 == B * 8 * C


def _loaded_cluster(engine="numpy"):
    cl = MemECCluster(num_servers=10, num_proxies=2, c=4, chunk_size=512,
                      max_unsealed=1, engine=engine)
    keys = [b"key%05d" % i for i in range(3000)]
    cl.multi_set([(k, b"v" * 8) for k in keys])
    return cl, keys


def test_recovery_split_adds_up_to_fail_server():
    cl, _ = _loaded_cluster()
    sid = max(range(10), key=lambda s: sum(cl.servers[s].sealed))
    t0 = time.perf_counter()
    timings = cl.fail_server(sid, recover=True)
    wall = time.perf_counter() - t0
    assert timings["recovered_chunks"] > 0
    w = cl.wall_stats
    parts = [w[f"recover_{p}_s"]
             for p in ("transition", "gather", "decode", "install")]
    assert all(p > 0 for p in parts), w
    assert w["recoveries"] == 1
    assert sum(parts) == pytest.approx(wall, rel=0.1)


def test_wall_clock_counters_stay_out_of_the_modeled_stats():
    cl, _ = _loaded_cluster()
    cl.fail_server(0, recover=True)
    assert not any(k.startswith("recover_") for k in cl.stats)


def test_update_path_spans_nest_on_one_host_line(tmp_path):
    from bench import spans as bench_spans
    from bench import trace as bench_trace
    cl, keys = _loaded_cluster(engine="pallas")
    items = [(k, b"w" * 8) for k in keys[::47]]
    cl.multi_update(items)                  # compile outside the trace
    items = [(k, b"x" * 8) for k, _ in items]
    h0, d0 = cl.engine.h2d_bytes, cl.engine.d2h_bytes
    c0, r0 = cl.engine.copies_at_dispatch, cl.engine.results_fetched
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.multi_update", ops=len(items)):
        assert all(cl.multi_update(items))
    jax.profiler.stop_trace()
    path = bench_spans.newest_traces(tmp_path)[0]

    lines = [line for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    line = next(ln for ln in lines if any(
        e.name == spans.STORE_MULTI_UPDATE for e in ln.events))
    events = {}
    for e in line.events:
        events.setdefault(e.name, []).append(e)
    (outer,) = events["bench.multi_update"]
    (store,) = events[spans.STORE_MULTI_UPDATE]
    assert outer.start_ns <= store.start_ns <= store.end_ns <= outer.end_ns
    for name in (spans.KERNEL_STAGE, spans.KERNEL_CALL, spans.ENGINE_WAIT,
                 spans.ENGINE_FETCH, spans.STORE_PARITY_GATHER,
                 spans.STORE_DELTA_LOG, spans.NETSIM):
        assert events.get(name), f"{name} not on the store's host line"
        for e in events[name]:
            assert store.start_ns <= e.start_ns <= e.end_ns <= store.end_ns
    # one wait and one fetch per resolved result, each result's copy
    # started at dispatch, and the fetch spans' bytes are d2h_bytes
    fetched = cl.engine.results_fetched - r0
    assert fetched == cl.engine.copies_at_dispatch - c0 > 0
    assert len(events[spans.ENGINE_WAIT]) == fetched
    assert len(events[spans.ENGINE_FETCH]) == fetched
    assert sum(dict(e.stats)["bytes"] for e in events[spans.ENGINE_FETCH]) \
        == cl.engine.d2h_bytes - d0
    (log,) = events[spans.STORE_DELTA_LOG]
    for name in (spans.ENGINE_WAIT, spans.ENGINE_FETCH):
        (e,) = events[name]
        assert log.start_ns <= e.start_ns <= e.end_ns <= log.end_ns

    red = bench_spans.reduce_file(path)
    assert red.ops == len(items)
    assert red.window_s == bench_trace.reduce_file(path).window_s
    assert red.nbytes[spans.KERNEL_STAGE] == cl.engine.h2d_bytes - h0 > 0
    assert red.nbytes[spans.ENGINE_FETCH] == cl.engine.d2h_bytes - d0 > 0
    # self times partition the store call: they add up to its duration
    total = sum(red.self_s.values())
    assert total == pytest.approx((store.end_ns - store.start_ns) / 1e9,
                                  rel=1e-6)
