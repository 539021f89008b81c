"""The benchmark's tests run on the CPU, with the store's sources on the
path and no ``MEMEC_*`` setting leaking in from the environment."""
import os
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))


@pytest.fixture(autouse=True)
def _no_store_knobs(monkeypatch):
    for k in [k for k in os.environ if k.startswith("MEMEC_")]:
        monkeypatch.delenv(k)


# a cell cut to the CPU: chunks seal after a few objects, windows stay
# small so few batch sizes compile
TINY = {"objects": 2000, "chunk_size": 512, "max_unsealed": 1}
TINY_TRAFFIC = {"rate_ops_per_s": 300, "max_window_ops": 8,
                "warmup_s": 0.5}


def tiny_run(workload: str, seed: int = 3, seconds: float = 1.5, **kw):
    from bench.harness import run_cell
    traffic = dict(TINY_TRAFFIC)
    if "degraded" in workload:
        traffic["window_reserve_s"] = 0.5
    return run_cell(workload, seed, seconds, kw.pop("trace", False),
                    require_tpu=False, expected_path="xla-compiled",
                    overrides=TINY, traffic_overrides=traffic, **kw)
