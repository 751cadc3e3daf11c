"""The command's refusals: no chip, or no program beside the benchmark,
give a non-zero exit and no result line."""
import os
import shutil
import subprocess
import sys

import pytest

from perfbench.harness import manifest as mf

ARGS = ["--workload", "serve.sasrec.saturated", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(root, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run([sys.executable, os.path.join(root, "perfbench", "run.py"),
                           *ARGS], cwd=root, env=env, capture_output=True, text=True,
                          timeout=240)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(mf.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_only_the_benchmark_files_exits_nonzero_without_a_result(tmp_path):
    shutil.copytree(mf.BENCH_DIR, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    p = _run(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("bad", [["--workload", "no.such.cell"], ["--seed", "x"]])
def test_bad_arguments_exit_nonzero(bad):
    from perfbench import run

    argv = list(ARGS)
    i = argv.index(bad[0])
    argv[i + 1] = bad[1]
    with pytest.raises((SystemExit, KeyError)):
        run.main(argv)
