"""BENCHMARK.json and the files it names: every part is found by name,
and the file keeps to the benchmark contract's shape."""
import json
import re

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level_keys(bench):
    assert list(bench) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51


def test_every_config_file_is_found_and_holds_its_cut(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        cfg = spec.config(bench, c["name"])
        assert set(c["reduced"]) <= set(cfg) and set(c["reduced"]) <= set(
            cfg["reduced"])
        spec.code(cfg["scheme"])    # its reference code exists
    assert len({c["source"] for c in bench["configs"]}) == \
        len(bench["configs"])


def test_every_cell_finds_its_config_and_mix(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names)
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    assert len(pairs) == len(names)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] == 1
        assert 1 <= len(w["why"]) <= 200
        spec.config(bench, w["config"])
        traffic, _ = spec.traffic(w["traffic"])
        assert traffic["rate_ops_per_s"] > 0


def test_every_metric_has_a_reader_and_sane_keys(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert callable(spec.reader(m["name"]))
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], m["layer"])
    setup = next(m for m in bench["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] <= 0.25


def test_every_cell_reports_setup_another_end_to_end_and_a_layer(bench):
    for w in bench["workloads"]:
        e2e = {m["name"] for m in spec.metrics_for(bench, w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        per = spec.metrics_for(bench, w["name"], True)
        assert per and all(m["moves"] in e2e for m in per)


def test_unknown_names_are_refused(bench):
    with pytest.raises(spec.SpecError):
        spec.cell(bench, "no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.traffic("no-such-mix")
    with pytest.raises(spec.SpecError):
        spec.reader("no_such_metric")


def test_file_is_small_and_plain(bench):
    text = json.dumps(bench)
    assert len(text) < 64 * 1024 and "\t" not in text
