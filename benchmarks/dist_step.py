"""Multi-device fused FOPO step benchmark — emits
results/BENCH_dist_step.json (via benchmarks.run).

Two kinds of rows:

  * analytic — `roofline.dist_comms_model` at paper shapes (S=1000,
    K=256, P=1M): collective bytes of the sharded step (retrieval
    K-merge, (B, S) id all-gather, THE score psum, grad psum) against
    the replicated-beta alternative's per-device HBM residency and
    gather traffic, with roofline-bandwidth step-time estimates. These
    are the catalog-scaling terms: beta residency and gather bytes
    drop n_model-fold, comms grow O(B(S+K)) — never O(P). The
    `_fsampler` twin of each row models fused_sampler=True under dist
    (landed PR 4): the jax.random (B, S, K) Gumbel round-trip —
    `sampler_gumbel_bytes`, ~8x the gather traffic at paper shapes —
    drops out of the per-step HBM budget entirely.
  * measured — a CPU-ONLY PROBE: dist-vs-single wall time and the
    parity error on a 4-way (2x2) host-CPU mesh, via the shared
    `benchmarks.dist_parity_probe` SUBPROCESS (the same probe the test
    suite's single-device fallback runs), which forces
    JAX_PLATFORMS=cpu and XLA_FLAGS=--xla_force_host_platform_device_
    count=4 so the parent process's jax is untouched. Even on a machine
    with a chip these rows are host-CPU, interpret-mode numbers (every
    row says ``probe=cpu-only``): a tracked witness that the dist step
    runs and matches (parity column), never a device time. The chip
    counterpart is ``python chip_smoke.py --four-chips`` (the fused
    step on a 2x2 mesh of four real chips against one chip).
"""
from __future__ import annotations

import os
import subprocess
import sys

from benchmarks.common import emit
from benchmarks.roofline import dist_comms_model

# (B_per_replica, S, K, L) at the paper's protocol; P = 1M catalog rows
PAPER_SHAPES = ((32, 1000, 256, 64), (32, 1000, 256, 128))
CATALOG = 1_000_000


def run() -> None:
    for b, s, k, l in PAPER_SHAPES:
        for n in (2, 4, 16):
            for fused_sampler in (False, True):
                m = dist_comms_model(
                    b, s, k, l, CATALOG, n, fused_sampler=fused_sampler
                )
                tag = "_fsampler" if fused_sampler else ""
                emit(
                    f"dist_comms_B{b}_S{s}_K{k}_L{l}_P{CATALOG}_n{n}{tag}",
                    1e6 * m["sharded_step_s"],
                    f"comms_bytes={m['comms_bytes']};"
                    f"id_allgather_bytes={m['id_allgather_bytes']};"
                    f"score_psum_bytes={m['score_psum_bytes']};"
                    f"beta_hbm_sharded={m['beta_hbm_sharded_bytes']};"
                    f"beta_hbm_replicated={m['beta_hbm_replicated_bytes']};"
                    f"gather_hbm_sharded={m['gather_hbm_sharded_bytes']};"
                    f"sampler_gumbel_bytes={m['sampler_gumbel_bytes']};"
                    f"sampler_hbm_bytes={m['sampler_hbm_bytes']};"
                    f"replicated_step_us={1e6 * m['replicated_step_s']:.1f};"
                    f"advantage={m['advantage']:.2f}x",
                )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        res = subprocess.run(
            [sys.executable, "-m", "benchmarks.dist_parity_probe"],
            capture_output=True,
            text=True,
            env={**os.environ, "PYTHONPATH": os.path.join(root, "src")},
            cwd=root,
            timeout=1200,
        )
    except subprocess.TimeoutExpired:
        emit("dist_step_cpu4", 0.0, "probe=cpu-only;FAILED:timeout after 1200s")
        return
    rows = [ln for ln in res.stdout.splitlines() if ln.startswith("ROW,")]
    if not rows:
        emit("dist_step_cpu4", 0.0, f"probe=cpu-only;FAILED:{res.stderr[-300:]}")
        return
    for ln in rows:
        _, name, us, derived = ln.split(",", 3)
        emit(name, float(us), f"probe=cpu-only;{derived}")


if __name__ == "__main__":
    run()  # emit() prints each row as it lands
