"""A configuration, a traffic mix and metrics added as new files and
entries are found, and run, without editing any file that is there."""
import filecmp
import json
import os
import shutil

import pytest

from perfbench.harness import manifest as mf

NEW_CELL = "serve.sasrec-narrow.half_rate"


@pytest.fixture
def extended(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(mf.BENCH_DIR, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    b = root / "perfbench"
    config = json.loads((b / "configs" / "sasrec.json").read_text())
    config.update(name="sasrec-narrow", num_blocks=1)
    (b / "configs" / "sasrec-narrow.json").write_text(json.dumps(config))
    mix = json.loads((b / "traffic" / "saturated.json").read_text())
    mix["rate"] = mix["rate"] / 2
    (b / "traffic" / "half_rate.json").write_text(json.dumps(mix))
    (b / "limits" / f"{NEW_CELL}.json").write_text(
        (b / "limits" / "serve.sasrec.saturated.json").read_text())
    (b / "metrics" / "serve_p50_ms.py").write_text(
        "import numpy as np\n\n\ndef read(ctx):\n"
        "    lat = ctx.host.get('latency_s')\n"
        "    return None if lat is None else float(np.median(lat)) * 1e3\n")
    (b / "metrics" / "batch_rows_mean.serve_burst.py").write_text(
        "import numpy as np\n\n\ndef read(ctx):\n"
        "    return float(np.mean(ctx.host['batch_size']))\n")
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "sasrec-narrow", "source": "https://arxiv.org/abs/1808.09781",
                         "file": "perfbench/configs/sasrec-narrow.json",
                         "reduced": ["num_blocks"], "why": "one block"})
    m["workloads"].append({"name": NEW_CELL, "config": "sasrec-narrow",
                           "traffic": "half_rate", "chips": 1, "why": "half rate"})
    m["end_to_end"].append({"name": "serve_p50_ms", "unit": "ms", "better": "lower",
                            "bound": 0.05, "source": "host_clock",
                            "workloads": [NEW_CELL]})
    m["per_layer"].append({"name": "batch_rows_mean.serve_burst", "unit": "rows",
                           "better": "higher", "source": "host_clock", "layer": "engine",
                           "moves": "serve_p50_ms", "workloads": [NEW_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return str(root)


def test_new_files_are_found_by_name(extended):
    m = mf.load_manifest(extended)
    cell = mf.workload(m, NEW_CELL)
    config, path = mf.config_file(m, cell, extended)
    assert config["num_blocks"] == 1
    assert mf.reference_module(config, path).make_params
    bench = os.path.join(extended, "perfbench")
    base = mf.traffic_file(mf.workload(m, "serve.sasrec.saturated"), bench)
    assert mf.traffic_file(cell, bench)["rate"] == base["rate"] / 2
    assert [x["name"] for x in mf.metrics_for(m, NEW_CELL, False)] == \
        ["setup_s", "serve_p50_ms"]
    assert [x["name"] for x in mf.metrics_for(m, NEW_CELL, True)] == \
        ["batch_rows_mean.serve_burst"]
    assert mf.metric_reader("serve_p50_ms", bench).read


def test_no_file_that_was_there_changed(extended):
    for dirpath, dirs, files in os.walk(mf.BENCH_DIR):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), mf.ROOT)
            assert filecmp.cmp(os.path.join(mf.ROOT, rel), os.path.join(extended, rel),
                               shallow=False), rel


def test_the_new_cell_runs(small_run, extended):
    rc, res = small_run(NEW_CELL, root=extended)
    assert rc == 0 and res["correct"] is True, res
    assert set(res["metrics"]) == {"setup_s", "serve_p50_ms"}
