"""BENCHMARK.json against the rules for its keys, names and bounds, and every file it names
present under bench/."""
import os
import re

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert bench["command"] == ["python3", "bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    # A full check of 24 cells: 2 + 14 * 24 runs of run_seconds + 60 s,
    # 2 * 90 s of compilation per cell and 1200 s spare, in 43200 s.
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names


def test_every_cell_has_its_files(bench):
    cells = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        entry = harness.find(bench["configs"], w["config"], "config")
        cfg, mod = harness.load_config(entry)
        assert cfg["name"] == entry["name"]
        assert set(entry["reduced"]) == set(cfg.get("reduced", []))
        assert hasattr(mod, "Program") and hasattr(mod, "Reference")
        root = harness.HERE
        assert os.path.exists(os.path.join(root, "traffic",
                                           f"{w['traffic']}.json"))
        assert harness.load_limits(w["name"])
    for m in bench["per_layer"]:
        assert set(m["workloads"]) <= cells
        mod = harness.load_module(os.path.join(
            harness.HERE, "metrics", f"{m['name']}.py"), m["name"])
        assert mod.UNIT == m["unit"]
        for cell in m["workloads"]:
            assert m["moves"] in harness.end_to_end_names(bench, cell)


def test_every_cell_reports_setup_and_another_metric(bench):
    for w in bench["workloads"]:
        e2e = harness.end_to_end_names(bench, w["name"])
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.per_layer_names(bench, w["name"], set(e2e))
