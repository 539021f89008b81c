"""Find the benchmark's parts by name.

``BENCHMARK.json`` at the repository root names the cells; a cell names
a configuration (its ``file``) and a traffic mix (``bench/traffic/<name>
.json``, with an optional hook ``bench/traffic/<name>.py``); each metric
is read by ``bench/metrics/<name>.py``, which defines ``read(run)``.
Adding a cell, a mix or a metric therefore adds files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


class SpecError(Exception):
    pass


def load_benchmark(path: Path | None = None) -> dict:
    path = path or REPO / "BENCHMARK.json"
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"{path} not found")


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r}; known: "
                    f"{sorted(e['name'] for e in entries)}")


def cell(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, name: str) -> dict:
    entry = _named(bench["configs"], name, "configuration")
    return json.loads((REPO / entry["file"]).read_text())


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise SpecError(f"{path} not found")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic(name: str) -> tuple[dict, object | None]:
    """The mix's parameters and its hook module (None without one)."""
    path = BENCH / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic mix {name!r} ({path})")
    hook = path.with_suffix(".py")
    return (json.loads(path.read_text()),
            load_module(hook, f"bench_traffic_{name}") if hook.is_file()
            else None)


def reader(name: str):
    """The ``read(run)`` function of metric ``name``."""
    return load_module(BENCH / "metrics" / f"{name}.py",
                       f"bench_metric_{name}").read


def metrics_for(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (trace off) or per-layer metrics
    (trace on): those listing the cell, and those listing no cells."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def code(scheme: str):
    """The plain reference code of a scheme, ``bench/codes/<scheme>.py``."""
    return load_module(BENCH / "codes" / f"{scheme}.py",
                       f"bench_code_{scheme}")
