"""BENCHMARK.json against the files it names: what the driver refuses
before a run is refused here first."""

import importlib
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert bench["paths"] == ["benchmark"] and 1 <= bench["run_seconds"] <= 51
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lines(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group if group in ("configs", "workloads") else "metric", e["name"]))
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}


def test_every_cell_finds_its_files_and_every_config_is_used(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        cell = load("workloads", f"{w['name']}.json")
        assert cell["config"] == w["config"] and cell["traffic"] == w["traffic"]
        assert NAME.match(w["traffic"])
        cfg = load("configs", f"{w['config']}.json")
        assert cfg["chips"] == w["chips"]
        traffic = load("traffic", f"{w['traffic']}.json")
        importlib.import_module(f"generators.{traffic['generator']}")
        used.add(w["config"])
    assert used == set(configs)
    for c in bench["configs"]:
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        cfg = load("configs", f"{c['name']}.json")
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        for key in ("assumed", "deployment", "serve_args"):
            assert key in cfg
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size|experts_per_tok)$", key)


def test_every_metric_has_a_reader_and_moves_something_its_cells_report(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["per_layer"]:
        spec = load("layer_metrics", f"{m['name']}.json")
        importlib.import_module(f"readers.{spec['reader']['kind']}")
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in target.get("workloads", cells), (m["name"], cell)
    for cell in cells:
        mine = [m for m in bench["end_to_end"] if cell in m.get("workloads", cells)]
        assert len(mine) >= 2  # setup_s and at least one other


def test_peaks_table_names_its_source():
    peaks = load("peaks.json")
    assert "TPU v5e" in peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["hbm_gbps"] == 819.0
