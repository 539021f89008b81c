"""chip_smoke.py: its refusals off the chip, and its phases at a small
size on the CPU's XLA path (the chip run itself needs a TPU)."""
import os
import shutil
import subprocess
import sys

import pytest

from conftest import subprocess_env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def run_smoke(*args, cwd=ROOT, **env_over):
    env = subprocess_env()
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("MEMEC_INTERPRET", None)
    env.update(env_over)
    return subprocess.run([sys.executable, os.path.basename(SMOKE), *args],
                          cwd=cwd, capture_output=True, text=True,
                          timeout=300, env=env)


def assert_refused(p, cause):
    assert p.returncode != 0
    assert cause in p.stderr, p.stderr[-2000:]
    assert '"ok"' not in p.stdout


def test_refuses_without_tpu():
    assert_refused(run_smoke(), "no TPU")


def test_refuses_interpret_mode():
    assert_refused(run_smoke(MEMEC_INTERPRET="1"), "MEMEC_INTERPRET")


def test_refuses_without_repository(tmp_path):
    shutil.copy(SMOKE, tmp_path)
    assert_refused(run_smoke(cwd=tmp_path), "sources not found")


@pytest.mark.parametrize("scheme", ["rs", "rdp"])
def test_store_phases_on_xla_path(scheme, monkeypatch, capsys):
    """60,000 objects seal a few hundred chunks, enough for every coding
    op (seal fold, RS batched UPDATE, recovery decode) to run."""
    monkeypatch.delenv("MEMEC_INTERPRET", raising=False)
    monkeypatch.setattr(chip_smoke, "EXPECTED_PATH", "xla-compiled")
    chip_smoke.run_store(scheme, 60_000, chip_smoke.CompileCounter(),
                         ops=4_000, degraded_reads=1_000, parity_sample=200)
    out = capsys.readouterr().out
    assert '"phase": "parity-check"' in out and '"bad": 0' in out


@pytest.mark.parametrize("scheme, op_paths, cause", [
    ("rs", {"matmul": "pallas-compiled", "delta_per_item": "pallas-compiled",
            "delta": "xla-compiled"}, "ops not on"),
    ("rdp", {"matmul": "pallas-compiled", "delta_per_item": "interpret"},
     "ops not on"),
    ("rs", {"matmul": "pallas-compiled", "delta_per_item": "pallas-compiled"},
     "never exercised"),
    ("rdp", {"delta_per_item": "pallas-compiled"}, "never exercised"),
])
def test_op_path_gate(scheme, op_paths, cause):
    with pytest.raises(chip_smoke.SmokeFailure, match=cause):
        chip_smoke.check_op_paths(scheme, scheme, op_paths)


def test_op_path_gate_accepts_compiled():
    chip_smoke.check_op_paths("rs", "rs", dict.fromkeys(
        ("matmul", "delta_per_item", "delta"), "pallas-compiled"))


def test_four_chip_phase_on_virtual_devices():
    # the chip path must not pull in the dry-run (it overwrites XLA_FLAGS
    # at import) or the model zoo
    code = ("import sys; sys.path.insert(0, '.'); import chip_smoke; "
            "chip_smoke.run_four_chips(pages_mib=2); "
            "import repro.configs.memec, repro.core.invariants, "
            "repro.data.ycsb; "
            "bad = [m for m in sys.modules if m.startswith(("
            "'repro.launch.dryrun', 'repro.models', 'repro.train'))]; "
            "print('FOUR_OK' if not bad else bad)")
    env = subprocess_env()
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=300, env=env)
    assert "FOUR_OK" in p.stdout, p.stderr[-2000:]
    assert p.stdout.count('"byte_equal": true') == 3
    assert '"devices": [0, 1, 2, 3]' in p.stdout
