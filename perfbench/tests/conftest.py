"""The harness's own tests run on the CPU at small sizes; they never load
the TPU runtime and write no compile cache into the checkout."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

from perfbench_small import CPU_PEAKS, SMALL, SMALL_TRAFFIC  # noqa: E402


@pytest.fixture
def small_run(capsys, monkeypatch, tmp_path):
    """Run one cell end to end at a small size on the CPU, skipping the
    look for a chip; returns (exit code, the parsed result line)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))

    def run(workload, *, root=ROOT, tamper=None, seconds=1.5, trace=0, seed=2**33 + 5):
        from perfbench import run as bench
        from perfbench.harness import manifest as mf

        m = mf.load_manifest(root)
        cell = mf.workload(m, workload)
        config, _ = mf.config_file(m, cell, root)
        tr = mf.traffic_file(cell, os.path.join(root, "perfbench"))
        rc = bench.main(
            ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
             "--trace", str(trace)],
            root=root, require_chip=False, config_override=SMALL[config["runner"]],
            traffic_override=SMALL_TRAFFIC[tr["kind"]], peaks=CPU_PEAKS, tamper=tamper,
            t_start=time.perf_counter())
        out = capsys.readouterr().out.strip().splitlines()
        return rc, (json.loads(out[-1]) if out else None)

    return run
