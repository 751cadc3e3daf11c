"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) accepts block shapes, slices
and primitives that the TPU compiler (Mosaic) refuses, so these tests
compile each kernel for a *described* v5e chip — nothing runs — at the
fopo-paper widths: B=32, S=1000 (tile-padded), L=100, P=750,000, K=256,
and an IVF index of 1024 lists of 1024 slots, or of 4096 slots as the
benchmark's index holds (16 cap tiles a list, so the clamped live-tile
index_maps compile at the benchmark's grid).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and the suite runs
under several pytest-xdist workers. The persistent compilation cache is
off around the compiles (a compile for a described chip cannot be read
back without one).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

B, S, L, P, K = 32, 1000, 100, 750_000, 256
C, CT, N_PROBE, DELTA_CAP = 1024, 256, 8, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, *args):
    """Lower + compile for the described chip; the compiled program must
    hold a Mosaic kernel (a silent fallback to XLA ops would not)."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _covgrad_args(spec):
    return (
        spec((B, L)), spec((P, L)), spec((B, S), jnp.int32),
        spec((B, S)), spec((B, S)),
    )


@pytest.mark.parametrize("tile", [8, 128])
@pytest.mark.parametrize("op", ["fused", "scores", "bwd", "step"])
def test_snis_covgrad_compiles_for_v5e(spec, op, tile):
    from repro.core.gradients import fused_covariance_loss
    from repro.kernels.snis_covgrad import ops

    h, beta, actions, log_q, rewards = _covgrad_args(spec)
    kw = dict(interpret=False, sample_tile=tile)
    if op == "fused":
        _compile(functools.partial(ops.snis_covgrad_fused, **kw),
                 h, beta, actions, log_q, rewards)
    elif op == "scores":
        _compile(functools.partial(ops.snis_scores_fused, **kw),
                 h, beta, actions, log_q, rewards)
    elif op == "bwd":
        _compile(functools.partial(ops.snis_covgrad_bwd, **kw),
                 log_q, actions, beta)
    else:  # the custom_vjp step: forward + backward kernels in one program
        def step(h, beta, actions, log_q, rewards):
            return jax.grad(
                lambda hh: fused_covariance_loss(
                    hh, beta, actions, log_q, rewards, **kw
                )[0]
            )(h)

        compiled = _compile(step, h, beta, actions, log_q, rewards)
        # well inside one chip's 16 GB, catalog lane-pad copies included
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 4 << 30


@pytest.mark.parametrize("tile", [8, 128])
def test_fused_sampler_compiles_for_v5e(spec, tile):
    from repro.kernels.fused_sampler import fused_mixture_sample

    def sample(key, idx, scores):
        return fused_mixture_sample(
            key, idx, scores, num_samples=S, epsilon=0.8, num_items=P,
            sample_tile=tile, interpret=False,
        )

    _compile(sample, spec((2,), jnp.uint32), spec((B, K), jnp.int32),
             spec((B, K)))


@pytest.mark.parametrize(
    "delta,cap",
    [
        pytest.param(False, 1024, id="False"),
        pytest.param(True, 1024, id="True"),
        pytest.param(False, 4096, id="False-cap4096"),
        pytest.param(True, 4096, id="True-cap4096"),
    ],
)
def test_ivf_topk_compiles_for_v5e(spec, delta, cap):
    from repro.kernels.ivf_topk import ops
    from repro.mips.ivf import IVFIndex

    def query(q, centroids, lists, list_embs, *d):
        index = IVFIndex(centroids, lists, list_embs, num_items=P)
        return ops.ivf_topk(
            q, index, K, n_probe=N_PROBE, cap_tile=CT, interpret=False,
            delta=d or None,
        )

    args = [spec((B, L)), spec((C, L)), spec((C, cap), jnp.int32),
            spec((C, cap, L))]
    if delta:
        args += [spec((C, DELTA_CAP), jnp.int32), spec((C, DELTA_CAP, L))]
    _compile(query, *args)


@pytest.mark.xfail(
    strict=True, raises=NotImplementedError,
    reason="mips_topk merges with lax.top_k in-kernel, which Mosaic has "
           "no lowering for (ivf_topk uses a bitonic merge instead)",
)
def test_mips_topk_compiles_for_v5e(spec):
    from repro.kernels.mips_topk import ops

    _compile(functools.partial(ops.mips_topk, k=K, interpret=False),
             spec((B, L)), spec((P, L)))
