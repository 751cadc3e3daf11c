"""Pallas TPU backward kernel for the fused FOPO step.

The surrogate loss is L = -(1/B) sum_b sum_s c_{bs} f_{bs} with the
SNIS covariance coefficients c treated as constants (Algorithm 1
evaluates the weights, it does not differentiate them), so

    dL/df_{bs} = -(1/B) g c_{bs}          (per-sample score gradient)
    dL/dh_b    = sum_s (dL/df_{bs}) beta_{a_bs}

i.e. the backward pass is a coefficient-weighted gather-reduce over the
same catalog rows the forward pass touched. Like the forward kernel the
gather happens in-kernel: actions are scalar-prefetched and each grid
step DMAs the (1, L) row they name from HBM into VMEM — nothing
(B, S, L)-shaped ever reaches HBM, and beta rows are read from HBM
exactly once per sample.

Grid: (B, S), S innermost. out[b] is a (1, L) accumulator revisited
across the S steps (sequential reduction, "arbitrary"); batch rows
touch disjoint output blocks, so the B axis is "parallel".

Masked slots (action < 0) carry c == 0 exactly (their SNIS weight is 0)
and are additionally skipped with pl.when, so the clamped row-0 DMA
issued for them never contributes.

`snis_covgrad_bwd_tiled_pallas` is the sample-tiled variant (grid
(B, Sp/TS)): TS catalog rows are regathered per step with overlapped
async copies into a (TS, L) VMEM tile — mirroring the tiled forward —
and the accumulate becomes one (1, TS) x (TS, L) matmul-shaped
contraction per tile instead of TS scalar-weighted row adds. Masked
lanes are zeroed structurally (coeff lane forced to 0 when the
prefetched action id is negative), so arbitrary caller coefficients on
dead slots never contribute, same contract as the per-sample kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.snis_covgrad.kernel import (
    _elem_spec,
    _row_spec,
    _tile_spec,
    lane_pad,
)


def _fused_bwd_kernel(
    actions_ref,  # [B, S] int32 scalar-prefetch (SMEM)
    coeff_ref,  # (1, 1) dL/df for sample (b, s)
    beta_hbm,  # [P, L] full catalog, memory_space=ANY
    grad_ref,  # (1, L) dL/dh_b accumulator
    beta_ref,  # (1, L) VMEM row: catalog row actions[b, s] (clamped)
    sem,  # DMA semaphore of the row copy
):
    b = pl.program_id(0)
    s = pl.program_id(1)
    row = jnp.maximum(actions_ref[b, s], 0)
    copy = pltpu.make_async_copy(beta_hbm.at[pl.ds(row, 1), :], beta_ref, sem)
    copy.start()

    @pl.when(s == 0)
    def _init():
        grad_ref[...] = jnp.zeros_like(grad_ref)

    copy.wait()

    @pl.when(actions_ref[b, s] >= 0)
    def _accum():
        grad_ref[...] += coeff_ref[0, 0] * beta_ref[...]


def snis_covgrad_bwd_pallas(
    coeff: jnp.ndarray,  # [B, S] per-sample score gradients dL/df
    actions: jnp.ndarray,  # [B, S] int32 item ids; -1 marks masked slots
    beta: jnp.ndarray,  # [P, L] fixed item embeddings (stays in HBM)
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """grad_h [B, L] = sum_s coeff[b, s] * beta[actions[b, s]]."""
    b, s = actions.shape
    l0 = beta.shape[-1]
    beta = lane_pad(beta)
    l = beta.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s),
        in_specs=[
            _elem_spec(),  # coeff elem
            pl.BlockSpec(memory_space=pl.ANY),  # full beta, gathered by DMA
        ],
        out_specs=_row_spec(l),
        scratch_shapes=[
            pltpu.VMEM((1, l), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        _fused_bwd_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, l), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(actions, coeff.reshape(b, s, 1, 1), beta).reshape(b, l)[:, :l0]


# ---------------------------------------------------------------------------
# sample-tiled variant — TS-row regather + one contraction per grid step
# ---------------------------------------------------------------------------

def _fused_bwd_tiled_kernel(
    actions_ref,  # [B, Sp] int32 scalar-prefetch (SMEM), Sp % TS == 0
    coeff_ref,  # (1, TS) dL/df tile
    beta_hbm,  # [P, L] full catalog, memory_space=ANY
    grad_ref,  # (1, L) dL/dh_b accumulator
    beta_tile,  # (TS, L) VMEM gather tile
    sem,  # DMA semaphore shared by the TS row copies
    *,
    sample_tile: int,
):
    i = pl.program_id(0)
    j = pl.program_id(1)

    def _row_copy(u):
        idx = jnp.maximum(actions_ref[i, j * sample_tile + u], 0)
        return pltpu.make_async_copy(
            beta_hbm.at[pl.ds(idx, 1), :], beta_tile.at[pl.ds(u, 1), :], sem
        )

    for u in range(sample_tile):
        _row_copy(u).start()

    @pl.when(j == 0)
    def _init():
        grad_ref[...] = jnp.zeros_like(grad_ref)

    for u in range(sample_tile):
        _row_copy(u).wait()

    # structural masking: a lane whose action id is negative contributes
    # exactly nothing, whatever coefficient the caller put there
    # (the (1, TS) id row is assembled from TS prefetched SMEM scalars by
    # lane selects: Mosaic cannot stack scalar booleans into a vector)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, sample_tile), 1)
    acts = jnp.full((1, sample_tile), -1, jnp.int32)
    for u in range(sample_tile):
        acts = jnp.where(lane == u, actions_ref[i, j * sample_tile + u], acts)
    coeff = jnp.where(acts >= 0, coeff_ref[...], 0.0)  # (1, TS)
    grad_ref[...] += jnp.dot(  # (1, TS) @ (TS, L), full f32 precision
        coeff, beta_tile[...], precision=jax.lax.Precision.HIGHEST
    )


def snis_covgrad_bwd_tiled_pallas(
    coeff: jnp.ndarray,  # [B, Sp] per-sample score gradients dL/df
    actions: jnp.ndarray,  # [B, Sp] int32 item ids; -1 marks masked slots
    beta: jnp.ndarray,  # [P, L] fixed item embeddings (stays in HBM)
    *,
    sample_tile: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Tiled twin of `snis_covgrad_bwd_pallas`; Sp % sample_tile == 0."""
    b, sp = actions.shape
    ts = sample_tile
    if sp % ts:
        raise ValueError(f"S={sp} must be padded to a multiple of TS={ts}")
    l0 = beta.shape[-1]
    beta = lane_pad(beta)
    l = beta.shape[-1]
    nj = sp // ts
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nj),
        in_specs=[
            _tile_spec(ts),  # coeff tile
            pl.BlockSpec(memory_space=pl.ANY),  # full beta, DMA-gathered
        ],
        out_specs=_row_spec(l),
        scratch_shapes=[
            pltpu.VMEM((ts, l), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        functools.partial(_fused_bwd_tiled_kernel, sample_tile=ts),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, l), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(actions, coeff.reshape(b, nj, 1, ts), beta).reshape(b, l)[:, :l0]
