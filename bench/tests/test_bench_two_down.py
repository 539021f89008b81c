"""The two-servers-down RDP cell cut to the CPU: a sound run is correct
and checks the chunks both failures rebuilt, each planted fault makes it
incorrect, and the degraded block's metric reads only from a trace."""
import json

import pytest

from bench import harness, spec
from bench.tests.conftest import tiny_run

CELL = "rdp-two-down-a"
SEED = 2**31 + 15


def test_sound_run_is_correct_and_checks_rebuilt_chunks(capfd):
    r = tiny_run(CELL, seed=SEED, seconds=2.5)
    assert r["correct"] and r["failed"] == 0
    assert r["checks"]["bad_rebuilt_chunks"]["value"] == 0
    (line,) = [ln for ln in capfd.readouterr().err.splitlines()
               if '"failed_servers"' in ln]
    logged = json.loads(line.removeprefix("bench: "))
    assert logged["failed_servers"] == [2, 3]
    assert logged["two_loss_rebuilds"] > 0
    assert logged["degraded_requests_since_open"] > 0
    assert logged["compiles_since_open"] == 0


@pytest.mark.parametrize("fault,check", [
    ("stale_parity", "bad_parity_stripes"),
    ("bad_rebuild", "bad_rebuilt_chunks"),
])
def test_a_planted_fault_makes_the_two_down_run_incorrect(fault, check):
    r = tiny_run(CELL, seed=SEED + 1, seconds=2.5, fault=fault)
    assert not r["correct"] and r["checks"][check]["value"] > 0


def test_degraded_ms_per_op_reads_none_without_a_trace():
    r = tiny_run(CELL, seed=SEED + 2, seconds=2.5)
    assert "degraded_ms_per_op" not in r["metrics"]
    run = harness.Run(setup_s=1.0, traffic_s=1.0, due=None, issue=None,
                      done=None, windows=None, dispatches_at_open=0,
                      compiles_in_window=0, recover_s=None,
                      trace_bounds=None, trace=None)
    assert spec.reader("degraded_ms_per_op")(run) is None


def test_traced_run_reads_the_degraded_block(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    r = tiny_run(CELL, seed=SEED + 3, seconds=2.5, trace=True)
    assert r["correct"]
    assert r["metrics"]["degraded_ms_per_op"]["value"] > 0
