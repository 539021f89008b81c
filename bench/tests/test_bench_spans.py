"""The program-span reduction (``bench/spans.py``): self time per span,
bytes per span and idle gaps charged to the innermost span, on a
hand-built trace; the readers of the metrics it feeds; and a traced run
of a cell cut to the CPU."""
from types import SimpleNamespace

import pytest

from bench import spans, spec
from bench.tests.conftest import tiny_run
from bench.tests.test_bench_trace import ev, line

SPAN_METRICS = ("store_self_ms_per_op", "netsim_ms_per_op",
                "stage_ms_per_op", "dispatch_ms_per_op",
                "device_wait_ms_per_op", "fetch_ms_per_op",
                "h2d_bytes_per_op", "d2h_bytes_per_op")


def hand_built(program=True):
    memec = [
        ev("memec.store.multi_update", 110, 290, ops=4),
        ev("memec.netsim", 120, 130),
        ev("memec.store.parity_gather", 130, 140),
        ev("memec.kernel.stage", 140, 150, bytes=300),
        ev("memec.kernel.call", 150, 160),
        ev("memec.store.delta_log", 160, 260),
        ev("memec.engine.wait", 165, 235),
        ev("memec.engine.fetch", 235, 245, bytes=200),
        ev("memec.store.multi_get", 305, 395, ops=6),
        ev("memec.netsim", 310, 320),
    ] if program else []
    host = SimpleNamespace(name="/host:CPU", lines=[line(
        "python",
        ev("bench.wait", 0, 100),
        ev("bench.multi_update", 100, 300, ops=4),
        ev("bench.multi_get", 300, 400, ops=6),
        ev("PjitFunction(f)", 152, 158),
        *memec)])
    device = SimpleNamespace(name="/device:TPU:0", lines=[
        line("XLA Modules", ev("jit_f(1)", 155, 200),
             ev("jit_g(2)", 180, 250)),
        line("XLA Ops")])
    return SimpleNamespace(planes=[host, device])


def test_self_time_bytes_and_idle_gaps_by_innermost_span():
    r = spans.reduce_profile(hand_built())
    assert r.window_s == pytest.approx(400e-9)
    assert r.ops == 10
    assert r.self_s == pytest.approx({
        "memec.store.multi_update": 40e-9,   # 180 less its 5 children
        "memec.netsim": 20e-9,
        "memec.store.parity_gather": 10e-9,
        "memec.kernel.stage": 10e-9,
        "memec.kernel.call": 10e-9,
        "memec.store.delta_log": 20e-9,      # 100 less wait and fetch
        "memec.engine.wait": 70e-9,
        "memec.engine.fetch": 10e-9,
        "memec.store.multi_get": 80e-9})
    assert r.total_self_s("memec.store") == pytest.approx(150e-9)
    assert r.calls["memec.netsim"] == 2
    assert r.nbytes == {"memec.kernel.stage": 300, "memec.engine.fetch": 200}
    # device busy [155, 250]: gaps [0, 155] and [250, 400]
    assert dict(r.program_idle_gaps) == pytest.approx({
        "bench.wait": 100e-9,
        "bench.multi_update": 20e-9,         # [100, 110] and [290, 300]
        "memec.store.multi_update": 40e-9,   # [110, 120] and [260, 290]
        "memec.netsim": 20e-9,
        "memec.store.parity_gather": 10e-9,
        "memec.kernel.stage": 10e-9,
        "memec.kernel.call": 5e-9,           # until the device starts
        "memec.store.delta_log": 10e-9,      # [250, 260]
        "bench.multi_get": 10e-9,
        "memec.store.multi_get": 80e-9})
    assert sum(v for _, v in r.program_idle_gaps) == pytest.approx(305e-9)


def test_a_trace_without_program_spans_charges_the_benchmark_spans():
    from bench import trace
    pd = hand_built(program=False)
    r = spans.reduce_profile(pd)
    assert r.self_s == {} and r.nbytes == {} and r.calls == {}
    assert r.total_self_s("memec.store") is None
    assert dict(r.program_idle_gaps) == pytest.approx(
        dict(trace.reduce_profile(pd).idle_gaps))


def test_a_span_cut_by_the_window_counts_only_inside_it():
    pd = hand_built()
    pd.planes[0].lines[0].events.append(ev("memec.store.multi_get", 396,
                                           450))
    r = spans.reduce_profile(pd)
    assert r.self_s["memec.store.multi_get"] == pytest.approx(84e-9)
    assert r.calls["memec.store.multi_get"] == 2


def _run(ops=10, traced=True):
    trace = SimpleNamespace(ops=ops, window_s=400e-9) if traced else None
    return SimpleNamespace(trace=trace, trace_bounds=None)


def test_readers_per_traced_op(monkeypatch):
    monkeypatch.setattr(spans, "of_run",
                        lambda run: spans.reduce_profile(hand_built()))
    got = {m: spec.reader(m)(_run()) for m in SPAN_METRICS}
    assert got == pytest.approx({
        "store_self_ms_per_op": 1.5e-5, "netsim_ms_per_op": 2e-6,
        "stage_ms_per_op": 1e-6, "dispatch_ms_per_op": 1e-6,
        "device_wait_ms_per_op": 7e-6, "fetch_ms_per_op": 1e-6,
        "h2d_bytes_per_op": 30.0, "d2h_bytes_per_op": 20.0})
    assert all(spec.reader(m)(_run(ops=0)) is None for m in SPAN_METRICS)


def test_readers_return_none_where_no_span_ran(monkeypatch):
    monkeypatch.setattr(spans, "of_run", lambda run: spans.reduce_profile(
        hand_built(program=False)))
    assert all(spec.reader(m)(_run()) is None for m in SPAN_METRICS)
    monkeypatch.setattr(spans, "of_run", lambda run: None)
    assert all(spec.reader(m)(_run()) is None for m in SPAN_METRICS)


def test_an_untraced_run_reads_no_trace():
    assert spans.of_run(_run(traced=False)) is None


def test_every_span_metric_is_declared_for_every_cell():
    bench = spec.load_benchmark()
    cells = [c["name"] for c in bench["workloads"]]
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in SPAN_METRICS:
        assert declared[name]["workloads"] == cells
        assert declared[name]["moves"] == "p50_ms"


def test_traced_cpu_run_reports_the_program_split():
    result = tiny_run("rs-a", seconds=2.0, trace=True)
    assert result["correct"]
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name in SPAN_METRICS:
        assert m.get(name, 0) > 0, name
    # the program's spans cover the store call the client times around
    # them, within the host time between the two
    assert m["store_self_ms_per_op"] < m["store_call_ms_per_op"]
