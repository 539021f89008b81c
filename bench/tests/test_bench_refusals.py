"""The run refuses, printing no result, where it must."""
import os
import shutil
import subprocess
import sys

import pytest

from bench.tests.conftest import REPO


def _run(env_extra, cwd=REPO, drop_memec=True):
    env = {k: v for k, v in os.environ.items()
           if not (drop_memec and k.startswith("MEMEC_"))}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rs-a", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_store_knob_in_the_environment_is_refused():
    r = _run({"MEMEC_ENGINE": "numpy"})
    assert r.returncode != 0 and r.stdout == ""
    assert "MEMEC_ENGINE" in r.stderr


def test_interpret_mode_forced_through_the_environment_is_refused():
    r = _run({"MEMEC_INTERPRET": "1"})
    assert r.returncode != 0 and r.stdout == ""


def test_no_tpu_is_refused():
    r = _run({})
    assert r.returncode != 0 and r.stdout == ""
    assert "no TPU" in r.stderr


def test_a_checkout_without_the_store_is_refused(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    r = _run({}, cwd=tmp_path)
    assert r.returncode != 0 and r.stdout == ""


def test_interpret_mode_forced_in_the_process_is_refused(monkeypatch):
    from bench.harness import BenchFailure, prepare
    from repro.kernels import dispatch
    monkeypatch.setattr(dispatch, "interpret_forced", lambda: True)
    with pytest.raises(BenchFailure, match="interpret"):
        prepare("rs-a", 1, require_tpu=False, expected_path="xla-compiled")
