"""BENCHMARK.json against the benchmark's contract, and the files each
of its names leads to."""
import json
import os
import re

import pytest

from perfbench.harness import manifest as mf

ROOT = mf.ROOT
M = mf.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
E2E = {m["name"]: m for m in M["end_to_end"]}
CELLS = [w["name"] for w in M["workloads"]]


def _reported(cell, traced):
    return {m["name"] for m in mf.metrics_for(M, cell, traced)}


def test_top_level_keys_and_size():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert M["command"] == ["python3", "perfbench/run.py"]
    assert M["paths"] == ["perfbench"]


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = M["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_exactly_the_contract_keys(section):
    for e in M[section]:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e["name"]


@pytest.mark.parametrize("section", sorted(KEYS))
def test_names_units_and_text(section):
    names = [e["name"] for e in M[section]]
    assert len(names) == len(set(names))
    for e in M[section]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]


def test_metric_names_are_unique_across_sections():
    names = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(names) == len(set(names))


def test_end_to_end_bounds_and_sources():
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for m in M["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in M["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    e2e = _reported(cell, traced=False)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert _reported(cell, traced=True)


@pytest.mark.parametrize("metric", [m["name"] for m in M["per_layer"]])
def test_each_layer_metric_moves_what_its_cells_report(metric):
    m = next(x for x in M["per_layer"] if x["name"] == metric)
    assert m["moves"] in E2E
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert m["moves"] in _reported(cell, traced=False), (metric, cell)


def test_layers_named_alike_and_on_one_line():
    layers = {m["layer"] for m in M["per_layer"]}
    assert layers <= {"kernels", "device", "engine", "trainer", "launch"}


def test_cells_configs_and_chips():
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in M["workloads"]} == {c["name"] for c in M["configs"]}
    assert all(w["chips"] in (1, 4) for w in M["workloads"])
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_every_name_leads_to_its_files(cell):
    w = mf.workload(M, cell)
    entry = mf.config_entry(M, w)
    assert entry["file"].startswith("perfbench/")
    config, path = mf.config_file(M, w)
    assert config["name"] == entry["name"]
    for key in entry["reduced"]:
        assert NAME.match(key)
    assert os.path.isfile(os.path.join(os.path.dirname(path), config["reference"]))
    assert os.path.isfile(os.path.join(mf.BENCH_DIR, "runners", config["runner"] + ".py"))
    assert mf.traffic_file(w)["kind"] in ("train_job", "open_loop")
    limits = json.load(open(os.path.join(mf.BENCH_DIR, "limits", cell + ".json")))
    assert all(v > 0 for v in limits.values())
    for traced in (False, True):
        for m in mf.metrics_for(M, cell, traced):
            assert hasattr(mf.metric_reader(m["name"]), "read"), m["name"]


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for dirpath, dirs, files in os.walk(mf.BENCH_DIR):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
            assert ok.match(rel), rel
