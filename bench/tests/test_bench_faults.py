"""Whole runs of cells cut to the CPU: a sound run is correct and prints
the contract's line; each fault planted under the timed path makes
``correct`` false through the check that should catch it."""
import numpy as np
import pytest

from bench import harness, reference, spec
from bench.tests.conftest import TINY, tiny_run

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_sound_run_is_correct_and_prints_the_contract_line():
    r = tiny_run("rs-a")
    assert list(r) == KEYS
    assert r["correct"] and r["failed"] == 0 and r["attempted"] == 450
    bench = spec.load_benchmark()
    want = {m["name"]: m["unit"]
            for m in spec.metrics_for(bench, "rs-a", False)}
    assert {k: v["unit"] for k, v in r["metrics"].items()} == want
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    assert all(set(c) == {"value", "limit"} for c in r["checks"].values())
    assert r["checks"]["bad_parity_stripes"]["value"] == 0


def test_traced_run_reports_layers_and_a_breakdown(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    r = tiny_run("rs-a", seed=4, trace=True)
    assert list(r) == KEYS[:5] + ["breakdown", "checks"] and r["correct"]
    assert {"queue_wait_ms", "tail_p99_ms", "store_call_ms_per_op",
            "device_calls_per_op", "compiles_in_window"} <= set(r["metrics"])
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("fault,check", [
    ("stale_parity", "bad_parity_stripes"),
    ("half_batch", "wrong_reads"),
    ("wrong_answer", "wrong_reads"),
])
def test_a_planted_fault_makes_the_run_incorrect(fault, check):
    r = tiny_run("rs-a", seed=5, fault=fault)
    assert not r["correct"] and r["checks"][check]["value"] > 0


def test_one_altered_answer_counts_once():
    r = tiny_run("rs-a", seed=6, fault="wrong_answer")
    assert r["checks"]["wrong_reads"]["value"] == 1


def test_degraded_cell_checks_its_rebuilt_chunks():
    r = tiny_run("rs-degraded-a", seed=7)
    assert r["correct"] and "recover_s" in r["metrics"]
    assert r["checks"]["bad_rebuilt_chunks"]["value"] == 0
    bad = tiny_run("rs-degraded-a", seed=7, fault="bad_rebuild")
    assert not bad["correct"]
    assert bad["checks"]["bad_rebuilt_chunks"]["value"] == 1


@pytest.mark.parametrize("config", ["memec-rs-10-8", "memec-rdp-10-8"])
def test_one_corrupted_parity_byte_is_caught(config):
    from repro.core.shard import make_cluster
    from bench.driver import CheckedClient
    cfg = {**spec.config(spec.load_benchmark(), config), **TINY,
           "engine": "numpy"}
    cluster = make_cluster(**harness.cluster_kwargs(cfg))
    harness.load(CheckedClient(cluster), cfg, seed=8)
    checked, bad = reference.check_parity(cluster, cfg)
    assert checked > 0 and bad == 0
    srv = next(s for s in cluster.servers
               if any(c is not None and c.position >= cfg["k"]
                      for c in s.chunk_ids))
    idx = next(i for i, c in enumerate(srv.chunk_ids)
               if c is not None and c.position >= cfg["k"])
    srv.region[idx][17] ^= np.uint8(0x40)
    assert reference.check_parity(cluster, cfg) == (checked, 1)
