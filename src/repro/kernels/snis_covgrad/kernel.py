"""Pallas TPU forward kernels: fused beta-gather + SNIS + covariance grad.

Two tilings of the same math live here:

* `snis_covgrad_fwd_pallas` — the per-sample kernel (grid (B, S), one
  (1, L) beta row DMA'd per step from the scalar-prefetched action id).
* `snis_covgrad_fwd_tiled_pallas` — the sample-tiled kernel (grid
  (B, S/TS)): each step gathers a *tile* of TS catalog rows into a
  (TS, L) VMEM block with explicit overlapped `make_async_copy` DMAs
  (embedding-bag-style multi-row prefetch), scores the whole tile as
  one (1, TS) x (TS, L) contraction, and folds it into the online
  softmax with ONE rescale per tile instead of one per sample. TS times
  fewer grid steps and TS in-flight row DMAs per step lift the DMA
  engine and MXU utilisation that the per-sample kernel leaves idle.

Callers pad S up to a multiple of TS (see ops.py); padded slots carry
``action = -1`` / ``log_q = LOG_Q_PAD`` and are forced to an exact-zero
SNIS weight in-kernel, so tails that don't divide the tile are exact.

Algorithm 1's per-example objective pieces are

    f_s   = h_b . beta_{a_s}                      (sampled scores)
    wbar  = softmax(f_s - log q_s)                (SNIS weights)
    rbar  = sum_s wbar_s r_s
    g_b   = sum_s wbar_s (r_s - rbar) beta_{a_s}  (covariance gradient)

The jnp formulation first materialises the gathered item embeddings
``beta[actions]`` — a (B, S, L) tensor — in HBM, then runs the chain as
five separate ops. Neither kernel lets that tensor exist: the action
indices are a **scalar-prefetch** operand (SMEM), and in-body async
copies stream exactly the referenced catalog rows HBM -> VMEM (one row
per step in the per-sample kernel, TS rows per step in the tiled one).

Grids are row-major with the sample axis innermost. Both axes are
"arbitrary": the softmax over S is computed *online* (flash-attention
style running max ``m``, normaliser ``z``, and rescaled accumulators),
and the scratch accumulators are shared across batch rows (reset at the
first sample step, finalised at the last), so no grid reordering is
legal.

Online covariance-gradient identity used at finalisation:

    g = (A - rbar * C) / z,   A = sum_s w_s r_s beta_{a_s},
                              C = sum_s w_s beta_{a_s},
    w_s = exp(f_s - log q_s - m),  z = sum_s w_s,  rbar = (sum w_s r_s)/z

Masked slots (action < 0, log_q = LOG_Q_PAD) gather row 0 harmlessly
(index clamped) and their weight is forced to an *exact* 0.0 by
comparing log_q against LOG_Q_VALID_MAX — not merely left to exp
underflow, which breaks down when *every* slot of a row is masked (the
running max then sits at the sentinel and each masked slot would carry
w = exp(0) = 1). With the explicit mask a fully padded row finalises
with z = 0 -> the 1e-30 floor, A = C = 0, and an exactly-zero grad row.

``compute_covgrad=False`` drops every accumulator (m/z/r scratch, A/C
vectors) and the (B, L) grad output — the custom_vjp forward pass only
needs the sampled scores (the backward kernel regathers beta on
demand, see `backward.py`), so the loss-only trace is a pure
gather-dot with no per-step scalar state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.constants import LOG_Q_VALID_MAX, NEG_INF


# Mosaic accepts a block only when each of its last two dims is a
# multiple of (8, 128) or spans the whole array dim. Per-row operands are
# therefore passed with unit dims inserted — h as [B, 1, L], per-sample
# elements as [B, S, 1, 1], per-tile lanes as [B, S/TS, 1, TS] — so the
# kernel body still sees the (1, L), (1, 1) and (1, TS) blocks it was
# written for, with the leading grid dims squeezed away.
def _row_spec(l: int) -> pl.BlockSpec:
    return pl.BlockSpec((None, 1, l), lambda i, j, *_: (i, 0, 0))


def _elem_spec() -> pl.BlockSpec:
    return pl.BlockSpec((None, None, 1, 1), lambda i, j, *_: (i, j, 0, 0))


def _tile_spec(ts: int) -> pl.BlockSpec:
    return pl.BlockSpec((None, None, 1, ts), lambda i, j, *_: (i, j, 0, 0))


def lane_pad(x: jnp.ndarray) -> jnp.ndarray:
    """Zero-pad the last (embedding) dim up to a multiple of 128 lanes.

    A row DMA out of HBM must span whole 128-lane tiles, so the catalog
    and the user rows enter the kernels lane-padded; the zero lanes add
    exactly nothing to any score or gradient and are cropped on return.
    A no-op when L is already a multiple of 128."""
    pad = (-x.shape[-1]) % 128
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _fused_fwd_kernel(
    actions_ref,  # [B, S] int32 scalar-prefetch (SMEM)
    h_ref,  # (1, L) user embedding row b
    logq_ref,  # (1, 1) log q(a_s|x_b); LOG_Q_PAD on masked slots
    rewards_ref,  # (1, 1)
    beta_hbm,  # [P, L] full catalog, memory_space=ANY (stays in HBM)
    *refs,
    compute_covgrad: bool,
):
    if compute_covgrad:
        (scores_ref, grad_ref, beta_ref, sem,
         m_ref, z_ref, r_ref, a_ref, c_ref) = refs
    else:
        scores_ref, beta_ref, sem = refs
    i = pl.program_id(0)
    s = pl.program_id(1)
    num_s = pl.num_programs(1)
    # the gather: DMA catalog row actions[b, s] (clamped, so masked -1
    # never reads out of bounds) into the (1, L) VMEM row
    row = jnp.maximum(actions_ref[i, s], 0)
    copy = pltpu.make_async_copy(beta_hbm.at[pl.ds(row, 1), :], beta_ref, sem)
    copy.start()
    copy.wait()

    # (1, 1) vector math throughout: Mosaic stores no scalars to VMEM
    score = jnp.sum(h_ref[...] * beta_ref[...], axis=-1, keepdims=True)
    scores_ref[...] = score
    if not compute_covgrad:  # loss-only trace: score + store, nothing else
        return

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        z_ref[...] = jnp.zeros_like(z_ref)
        r_ref[...] = jnp.zeros_like(r_ref)
        a_ref[...] = jnp.zeros_like(a_ref)
        c_ref[...] = jnp.zeros_like(c_ref)

    logq = logq_ref[...]
    logw = jnp.where(logq < LOG_Q_VALID_MAX, score - logq, NEG_INF)
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, logw)
    alpha = jnp.exp(m_old - m_new)  # rescale of everything accumulated so far
    # exact-zero weight on masked slots (robust to all-masked rows where
    # m never leaves the sentinel and exp(logw - m) would be 1, not 0)
    w = jnp.where(logq < LOG_Q_VALID_MAX, jnp.exp(logw - m_new), 0.0)
    r = rewards_ref[...]
    z_ref[...] = z_ref[...] * alpha + w
    r_ref[...] = r_ref[...] * alpha + w * r
    m_ref[...] = m_new
    a_ref[...] = a_ref[...] * alpha + (w * r) * beta_ref[...]
    c_ref[...] = c_ref[...] * alpha + w * beta_ref[...]

    @pl.when(s == num_s - 1)
    def _finalize():
        z = jnp.maximum(z_ref[...], 1e-30)
        rbar = r_ref[...] / z
        grad_ref[...] = (a_ref[...] - rbar * c_ref[...]) / z


def snis_covgrad_fwd_pallas(
    h: jnp.ndarray,  # [B, L] user embeddings
    beta: jnp.ndarray,  # [P, L] fixed item embeddings (stays in HBM)
    actions: jnp.ndarray,  # [B, S] int32 item ids; -1 marks masked slots
    log_q: jnp.ndarray,  # [B, S]; LOG_Q_PAD on masked slots
    rewards: jnp.ndarray,  # [B, S]
    *,
    compute_covgrad: bool = True,
    interpret: bool = False,
):
    """Returns (scores [B, S], grad [B, L]) or just scores when
    ``compute_covgrad=False``. The (B, S, L) gathered-embedding tensor
    never exists in HBM — beta rows stream HBM -> VMEM one at a time."""
    b, s = actions.shape
    l0 = beta.shape[-1]
    h, beta = lane_pad(h), lane_pad(beta)
    l = beta.shape[-1]
    kernel = functools.partial(_fused_fwd_kernel, compute_covgrad=compute_covgrad)

    out_specs = [_elem_spec()]  # scores
    out_shape = [jax.ShapeDtypeStruct((b, s, 1, 1), jnp.float32)]
    scratch = [
        pltpu.VMEM((1, l), jnp.float32),  # gathered beta row
        pltpu.SemaphoreType.DMA,
    ]  # loss-only trace carries no accumulator state beyond the row
    if compute_covgrad:
        out_specs.append(_row_spec(l))  # grad
        out_shape.append(jax.ShapeDtypeStruct((b, 1, l), jnp.float32))
        scratch += [
            pltpu.VMEM((1, 1), jnp.float32),  # m — running max
            pltpu.VMEM((1, 1), jnp.float32),  # z — running normaliser
            pltpu.VMEM((1, 1), jnp.float32),  # r — running sum w*r
            pltpu.VMEM((1, l), jnp.float32),  # A — sum w*r*beta
            pltpu.VMEM((1, l), jnp.float32),  # C — sum w*beta
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s),
        in_specs=[
            _row_spec(l),  # h row (resident)
            _elem_spec(),  # log_q elem
            _elem_spec(),  # reward elem
            pl.BlockSpec(memory_space=pl.ANY),  # full beta, gathered by DMA
        ],
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(actions, h.reshape(b, 1, l), log_q.reshape(b, s, 1, 1),
      rewards.reshape(b, s, 1, 1), beta)
    scores = out[0].reshape(b, s)
    if compute_covgrad:
        return scores, out[1].reshape(b, l)[:, :l0]
    return scores


# ---------------------------------------------------------------------------
# sample-tiled variant — TS catalog rows gathered + folded per grid step
# ---------------------------------------------------------------------------

def _fused_fwd_tiled_kernel(
    actions_ref,  # [B, Sp] int32 scalar-prefetch (SMEM), Sp % TS == 0
    h_ref,  # (1, L) user embedding row b (resident across sample tiles)
    logq_ref,  # (1, TS) log q tile; LOG_Q_PAD on masked slots
    rewards_ref,  # (1, TS)
    beta_hbm,  # [P, L] full catalog, memory_space=ANY (stays in HBM)
    *refs,
    sample_tile: int,
    compute_covgrad: bool,
):
    if compute_covgrad:
        (scores_ref, grad_ref, beta_tile, sem,
         m_ref, z_ref, r_ref, a_ref, c_ref) = refs
    else:
        scores_ref, beta_tile, sem = refs
    i = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)

    # multi-row gather: TS overlapped row DMAs HBM -> VMEM tile. All
    # copies are started before any wait so the DMA engine pipelines
    # them (the per-sample kernel can only ever have one in flight).
    def _row_copy(u):
        idx = jnp.maximum(actions_ref[i, j * sample_tile + u], 0)
        return pltpu.make_async_copy(
            beta_hbm.at[pl.ds(idx, 1), :], beta_tile.at[pl.ds(u, 1), :], sem
        )

    for u in range(sample_tile):
        _row_copy(u).start()
    for u in range(sample_tile):
        _row_copy(u).wait()

    tile = beta_tile[...]  # (TS, L)
    # all TS sampled scores as one contraction against the resident h row
    scores = jnp.sum(tile * h_ref[...], axis=-1)[None, :]  # (1, TS)
    scores_ref[...] = scores
    if not compute_covgrad:
        return

    @pl.when(j == 0)
    def _init():
        m_ref[0, 0] = NEG_INF
        z_ref[0, 0] = 0.0
        r_ref[0, 0] = 0.0
        a_ref[...] = jnp.zeros_like(a_ref)
        c_ref[...] = jnp.zeros_like(c_ref)

    logq = logq_ref[...]  # (1, TS)
    valid = logq < LOG_Q_VALID_MAX
    logw = jnp.where(valid, scores - logq, NEG_INF)
    m_old = m_ref[0, 0]
    m_new = jnp.maximum(m_old, jnp.max(logw))  # ONE rescale per tile
    alpha = jnp.exp(m_old - m_new)
    w = jnp.where(valid, jnp.exp(logw - m_new), 0.0)  # (1, TS)
    r = rewards_ref[...]
    z_ref[0, 0] = z_ref[0, 0] * alpha + jnp.sum(w)
    r_ref[0, 0] = r_ref[0, 0] * alpha + jnp.sum(w * r)
    m_ref[0, 0] = m_new
    # (1, TS) @ (TS, L) — matmul-shaped accumulator folds, MXU-friendly,
    # at full f32 precision (the accumulators are the gradient itself)
    hi = jax.lax.Precision.HIGHEST
    a_ref[...] = a_ref[...] * alpha + jnp.dot(w * r, tile, precision=hi)
    c_ref[...] = c_ref[...] * alpha + jnp.dot(w, tile, precision=hi)

    @pl.when(j == num_j - 1)
    def _finalize():
        z = jnp.maximum(z_ref[0, 0], 1e-30)
        rbar = r_ref[0, 0] / z
        grad_ref[...] = (a_ref[...] - rbar * c_ref[...]) / z


def snis_covgrad_fwd_tiled_pallas(
    h: jnp.ndarray,  # [B, L] user embeddings
    beta: jnp.ndarray,  # [P, L] fixed item embeddings (stays in HBM)
    actions: jnp.ndarray,  # [B, Sp] int32; -1 marks masked slots
    log_q: jnp.ndarray,  # [B, Sp]; LOG_Q_PAD on masked slots
    rewards: jnp.ndarray,  # [B, Sp]
    *,
    sample_tile: int,
    compute_covgrad: bool = True,
    interpret: bool = False,
):
    """Tiled twin of `snis_covgrad_fwd_pallas`: grid (B, Sp/TS), a
    (TS, L) gather tile per step. Requires Sp % sample_tile == 0 (ops.py
    pads); returns (scores [B, Sp], grad [B, L]) or just scores."""
    b, sp = actions.shape
    ts = sample_tile
    if sp % ts:
        raise ValueError(f"S={sp} must be padded to a multiple of TS={ts}")
    l0 = beta.shape[-1]
    h, beta = lane_pad(h), lane_pad(beta)
    l = beta.shape[-1]
    kernel = functools.partial(
        _fused_fwd_tiled_kernel, sample_tile=ts, compute_covgrad=compute_covgrad
    )

    nj = sp // ts
    out_specs = [_tile_spec(ts)]  # scores
    out_shape = [jax.ShapeDtypeStruct((b, nj, 1, ts), jnp.float32)]
    scratch = [
        pltpu.VMEM((ts, l), jnp.float32),  # gathered beta tile
        pltpu.SemaphoreType.DMA,  # shared by the TS in-flight row copies
    ]
    if compute_covgrad:
        out_specs.append(_row_spec(l))  # grad
        out_shape.append(jax.ShapeDtypeStruct((b, 1, l), jnp.float32))
        scratch += [
            pltpu.SMEM((1, 1), jnp.float32),  # m — running max
            pltpu.SMEM((1, 1), jnp.float32),  # z — running normaliser
            pltpu.SMEM((1, 1), jnp.float32),  # r — running sum w*r
            pltpu.VMEM((1, l), jnp.float32),  # A — sum w*r*beta
            pltpu.VMEM((1, l), jnp.float32),  # C — sum w*beta
        ]
        # scratch order expected by the kernel: tile, sem, m, z, r, A, C
        # (outputs come first in *refs, then scratch in declaration order)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nj),
        in_specs=[
            _row_spec(l),  # h row (resident)
            _tile_spec(ts),  # log_q tile
            _tile_spec(ts),  # reward tile
            pl.BlockSpec(memory_space=pl.ANY),  # full beta, gathered by DMA
        ],
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(actions, h.reshape(b, 1, l), log_q.reshape(b, nj, 1, ts),
      rewards.reshape(b, nj, 1, ts), beta)
    scores = out[0].reshape(b, sp)
    if compute_covgrad:
        return scores, out[1].reshape(b, l)[:, :l0]
    return scores
