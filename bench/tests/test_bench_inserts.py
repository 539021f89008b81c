"""Inserts of new keys and YCSB's ``latest`` request distribution in the
benchmark's streams, and the store's counters on the run record.

A mix with neither must give the streams it gave before either existed:
the digests below were taken from ``make_stream`` before inserts and
``latest`` were added, and hold the five cells' traffic to them."""
import hashlib
import json

import numpy as np
import pytest

from bench import harness, spec
from bench.stream import (GET, SET, make_stream, rng_for,
                          value_size)
from bench.tests.conftest import TINY, TINY_TRAFFIC, tiny_run

CFG = {"objects": 1000, "value_sizes": [8, 32]}
LOAD = {"mix": {"insert": 1.0}, "zipf_theta": 0.99}
D = {"mix": {"get": 0.95, "insert": 0.05}, "zipf_theta": 0.99,
     "request_distribution": "latest"}

PARENT_DIGESTS = {
    ("ycsb-a-rs", 0): "bfb07d6db76b46be",
    ("ycsb-a-rs", 1): "7cd56efe3e7df88e",
    ("ycsb-a-rs", 7): "7c78f474a809aeff",
    ("ycsb-a-rdp", 0): "e96f2be76eae2789",
    ("ycsb-a-rdp", 1): "6bd6709a2e612567",
    ("ycsb-a-rdp", 7): "06b6ad3aea5835f5",
    ("ycsb-a-degraded", 0): "ac19fa462f07cb0e",
    ("ycsb-a-degraded", 1): "04e0195e4cae6f2d",
    ("ycsb-a-degraded", 7): "8dcbff6fddfcab12",
    ("ycsb-b-rs", 0): "3f636e17de5d8fc2",
    ("ycsb-b-rs", 1): "5037088a704a5f01",
    ("ycsb-b-rs", 7): "a0d28156807e0f36",
    ("ycsb-a-rdp-two-down", 0): "39ac4634ad5cff87",
    ("ycsb-a-rdp-two-down", 1): "02a74b5a84667170",
    ("ycsb-a-rdp-two-down", 7): "dbb8ae1368d50836",
}


def digest(s) -> str:
    h = hashlib.sha256()
    for a in (s.due, s.kind, s.ids):
        h.update(np.ascontiguousarray(a).tobytes())
    for v in s.values:
        h.update(b"-" if v is None else b"+%d:" % len(v) + v)
    return h.hexdigest()[:16]


@pytest.mark.parametrize("traffic,seed", sorted(PARENT_DIGESTS))
def test_existing_mixes_give_the_streams_they_gave_before(traffic, seed):
    bench = spec.load_benchmark()
    entry = next(w for w in bench["workloads"] if w["traffic"] == traffic)
    cfg = spec.config(bench, entry["config"])
    mix, _ = spec.traffic(traffic)
    s = make_stream(cfg, mix, mix["rate_ops_per_s"], 2.0, rng_for(seed, 2))
    assert s.inserts == 0
    assert digest(s) == PARENT_DIGESTS[(traffic, seed)]


def test_inserts_take_fresh_consecutive_ids_in_due_order():
    s = make_stream(CFG, LOAD, 1000, 2.0, rng_for(2**31 + 9, 2),
                    first_insert_id=1500)
    assert s.inserts == len(s) == 2000
    assert np.all(s.kind == SET)
    assert np.array_equal(s.ids, 1500 + np.arange(2000))
    assert all(len(v) == value_size(CFG, int(i))
               for i, v in zip(s.ids, s.values))
    d = make_stream(CFG, {**D, "request_distribution": "zipfian"}, 1000,
                    2.0, rng_for(2**31 + 9, 2))
    ins = d.ids >= CFG["objects"]
    assert d.inserts == ins.sum() == 100 and np.all(d.kind[ins] == SET)
    assert np.array_equal(d.ids[ins], CFG["objects"] + np.arange(100))
    assert np.all(d.kind[~ins] == GET) and d.ids.min() >= 0


def test_latest_reads_only_ids_written_before_and_the_newest_most():
    s = make_stream(CFG, D, 1000, 5.0, rng_for(2**31 + 5, 2),
                    first_insert_id=1200)
    ins = s.kind == SET
    newest = 1199 + np.cumsum(ins) - ins     # before each op
    reads = ~ins
    assert np.all(s.ids[reads] <= newest[reads])
    assert s.ids[reads].min() >= 1200 - CFG["objects"]
    got, count = np.unique(s.ids[reads], return_counts=True)
    top = got[np.argsort(count)[::-1][:10]]
    assert np.all(top >= 1200), top
    assert (s.ids[reads] >= 1200).mean() > 0.5
    # the same draws under zipfian put the load's first ids on top
    z = make_stream(CFG, {**D, "request_distribution": "zipfian"}, 1000,
                    5.0, rng_for(2**31 + 5, 2), first_insert_id=1200)
    assert np.array_equal(z.due, s.due) and np.array_equal(z.kind, s.kind)
    assert z.ids[~ins].max() < CFG["objects"]


@pytest.mark.parametrize("traffic,error", [
    ({"mix": {"get": 0.5, "insrt": 0.5}, "zipf_theta": 0.99}, "insrt"),
    ({**D, "request_distribution": "uniform"}, "uniform"),
])
def test_an_unknown_kind_or_distribution_is_refused(traffic, error):
    with pytest.raises(ValueError, match=error):
        make_stream(CFG, traffic, 100, 1.0, rng_for(1, 2))


def test_required_ops_leave_out_what_no_kind_of_the_mix_runs():
    cfg = {"required_ops": ["delta", "delta_per_item"]}
    a = {"mix": {"get": 0.5, "update": 0.5}}
    assert harness.required_ops(cfg, a) == {"delta", "delta_per_item"}
    assert harness.required_ops(cfg, LOAD) == {"delta_per_item"}
    assert harness.required_ops(cfg, {**D, "required_ops": ["matmul"]}) \
        == {"delta_per_item", "matmul"}


def _streams_driven(monkeypatch, *modules):
    """Record every stream the ``drive`` of ``modules`` serves."""
    from bench.driver import drive as real
    seen = []

    def drive(client, stream, **kw):
        seen.append(stream)
        return real(client, stream, **kw)
    for module in modules:
        monkeypatch.setattr(module, "drive", drive)
    return seen


def _window_log(err: str) -> dict:
    line = next(x for x in err.splitlines()
                if x.startswith('bench: {"window_s"'))
    return json.loads(line[len("bench: "):])


@pytest.mark.parametrize("name,traffic", [("load", LOAD), ("d", D)])
def test_an_insert_run_is_correct_and_seals_in_its_window(
        name, traffic, monkeypatch, capsys):
    seen = _streams_driven(monkeypatch, harness)
    r = harness.run_cell("rs-a", 2**31 + 11, 1.5, False, require_tpu=False,
                         expected_path="xla-compiled", overrides=TINY,
                         traffic_overrides={**TINY_TRAFFIC, **traffic})
    assert r["correct"] and r["failed"] == 0
    assert r["checks"]["no_stripes_checked"]["value"] == 0
    assert r["checks"]["bad_parity_stripes"]["value"] == 0
    sealed = _window_log(capsys.readouterr().err)["sealed_data_chunks"]
    assert sealed[1] > sealed[0]
    warm, window = seen
    assert warm.inserts > 0 and window.inserts > 0
    # every insert (the mixes' only SETs) took the next fresh id
    ids = np.concatenate([s.ids[s.kind == SET] for s in seen])
    assert np.array_equal(ids, TINY["objects"] + np.arange(len(ids)))


def test_sweep_rates_insert_after_the_warm_up_and_each_other(monkeypatch,
                                                             capsys):
    from bench import driver, run, sweep
    # the sweep's start-up turns on the compile cache process-wide
    monkeypatch.setattr(run, "configure", lambda: None)
    real = harness.prepare

    def prepare(workload, seed, traffic_overrides=None):
        return real(workload, seed, require_tpu=False,
                    expected_path="xla-compiled", overrides=TINY,
                    traffic_overrides={**TINY_TRAFFIC, **traffic_overrides})
    monkeypatch.setattr(harness, "prepare", prepare)
    seen = _streams_driven(monkeypatch, harness, driver)
    assert sweep.main(["--workload", "rs-a", "--seed", "5", "--seconds",
                       "1", "--rates", "100,200", "--traffic-overrides",
                       json.dumps({"mix": {"insert": 1.0}})]) == 0
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [r["rate"] for r in rows] == [100, 200]
    assert all(r["wrong_reads"] == 0 and r["refused"] == 0 for r in rows)
    assert [s.inserts for s in seen] == [50, 100, 200]   # warm-up, rates
    ids = np.concatenate([s.ids for s in seen])
    assert np.array_equal(ids, TINY["objects"] + np.arange(350))


def test_run_counters_hold_the_window_change_of_every_counter(monkeypatch):
    runs = []
    real = spec.reader

    def reader(name):
        def read(run):
            runs.append(run)
            return real(name)(run)
        return read
    monkeypatch.setattr(spec, "reader", reader)
    r = tiny_run("rs-a", seed=12)
    assert r["correct"]
    run = runs[0]
    w = run.windows
    assert run.counters["device_dispatches"] == \
        w[-1, 4] - run.dispatches_at_open > 0
    # the store's stats, its wall_stats and the engine's stats(), by name
    assert {"reconstructions", "recoveries", "h2d_bytes",
            "results_fetched"} <= set(run.counters)
    assert run.counters["recoveries"] == 0
    assert run.counters["h2d_bytes"] > 0
    assert all(isinstance(v, (int, float)) for v in run.counters.values())
