"""BENCHMARK.json keeps the benchmark's contract, and every name in it has
its file."""
import json
import os
import re

import pytest

from rbench import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]]


def _metrics_of(cell, kind):
    return [m for m in SPEC[kind]
            if "workloads" not in m or cell in m["workloads"]]


def test_top_level_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_entries_and_names():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for kind, want in keys.items():
        names = [e["name"] for e in SPEC[kind]]
        assert len(names) == len(set(names))
        for e in SPEC[kind]:
            assert set(e) - {"workloads"} == want, e
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_and_metrics(cell):
    loaded = harness.load_cell(cell)
    assert loaded.chips == 1
    e2e = {m["name"] for m in _metrics_of(cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = _metrics_of(cell, "per_layer")
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e, (cell, m["name"])
    for key in ("n", "m", "d_in", "d_hidden", "n_classes", "n_layers",
                "limits", "reduced", "assumed", "source"):
        assert key in loaded.config


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_metric_reader_files(metric):
    path = os.path.join(BENCH, "metrics", metric + ".py")
    src = open(path).read()
    entry = next(m for m in SPEC["per_layer"] if m["name"] == metric)
    assert f'LAYER = "{entry["layer"]}"' in src
    assert f'MOVES = "{entry["moves"]}"' in src
    assert callable(harness.metric_reader(BENCH, metric))


def test_configs_are_used_and_files_unique():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
