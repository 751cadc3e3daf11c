"""The control (the plain reference one precision below the
configuration's, put in the program's place) against a sound run of the
program, at a small size on the CPU: the control must read at least ten
times what the sound run reads on one of the cell's numbers, and each
planted fault must fail the cell's limits. At the cells' own size on the
chip the control fails the limits themselves (PERF.md gives those
readings)."""
import json
import os

import pytest
from perfbench_small import SMALL, SMALL_TRAFFIC

from perfbench import control
from perfbench.harness import manifest as mf

M = mf.load_manifest()


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_control_separates_and_faults_fail(cell, small_run):
    rc, sound = small_run(cell)
    assert rc == 0 and sound["correct"] is True
    w = mf.workload(M, cell)
    config, _ = mf.config_file(M, w)
    r = control.readings(cell, 2**33 + 5, config_override=SMALL[config["runner"]],
                         traffic_override=SMALL_TRAFFIC[mf.traffic_file(w)["kind"]])
    with open(os.path.join(mf.BENCH_DIR, "limits", cell + ".json")) as f:
        limits = json.load(f)
    ctl = r.pop("control")
    assert any(v >= 10 * sound["checks"][k]["value"] for k, v in ctl.items()), (ctl, sound)
    for fault, readings in r.items():
        assert any(v > limits[k] for k, v in readings.items()), (fault, readings, limits)
