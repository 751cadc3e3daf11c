"""Finds everything a cell needs by the names in `BENCHMARK.json`.

* a configuration: the file its entry names, and beside it the plain
  reference the file's ``reference`` key names;
* a traffic mix: ``perfbench/traffic/<traffic>.json``;
* a runner, one per kind of system: ``perfbench/runners/<runner>.py``,
  named by the configuration's ``runner`` key;
* a metric: ``perfbench/metrics/<metric name>.py``, a reader with
  ``read(ctx) -> float | None``.

So a later change adds a configuration, a mix or a metric by adding a
file and an entry, and edits no file that is here.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(manifest: dict, name: str) -> dict:
    return _by_name(manifest["workloads"], name, "workload")


def config_entry(manifest: dict, cell: dict) -> dict:
    return _by_name(manifest["configs"], cell["config"], "config")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def config_file(manifest: dict, cell: dict, root: str = ROOT) -> tuple[dict, str]:
    """(the configuration as run, the path of its file)."""
    path = os.path.join(root, config_entry(manifest, cell)["file"])
    return load_json(path), path


def traffic_file(cell: dict, bench_dir: str = BENCH_DIR) -> dict:
    return load_json(os.path.join(bench_dir, "traffic", cell["traffic"] + ".json"))


def load_module(path: str, name: str | None = None):
    """Import a file by path (names may hold '.' and '-')."""
    mod_name = name or "perfbench_dyn_" + "".join(
        ch if ch.isalnum() else "_" for ch in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def reference_module(config: dict, config_path: str):
    return load_module(os.path.join(os.path.dirname(config_path), config["reference"]))


def runner_module(config: dict, bench_dir: str = BENCH_DIR):
    return load_module(os.path.join(bench_dir, "runners", config["runner"] + ".py"))


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    return load_module(os.path.join(bench_dir, "metrics", name + ".py"))


def metrics_for(manifest: dict, cell_name: str, traced: bool) -> list[dict]:
    """The cell's end-to-end metrics (untraced run) or per-layer metrics
    (traced run): every entry whose ``workloads`` lists the cell, or
    that has no ``workloads`` key."""
    entries = manifest["per_layer"] if traced else manifest["end_to_end"]
    return [m for m in entries if cell_name in m.get("workloads", [cell_name])]
