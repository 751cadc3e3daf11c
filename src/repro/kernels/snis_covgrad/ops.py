"""jit'd public wrappers for the fused SNIS covariance-gradient kernels.

``sample_tile`` selects the kernel tiling: ``sample_tile <= 1`` runs
the per-sample kernels (grid (B, S), one (1, L) row DMA per step);
``sample_tile = TS > 1`` runs the tiled kernels (grid (B, ceil(S/TS)),
a (TS, L) multi-row gather tile + one online-softmax rescale per step).
S is padded here up to a multiple of TS with dead slots — ``action =
-1`` / ``log_q = LOG_Q_PAD`` / ``reward = 0`` — which carry an *exact*
zero SNIS weight in-kernel, so tails that don't divide the tile are
bit-for-bit harmless; padded score columns are cropped before return.

``interpret=None`` (the default) takes the backend rule of
`repro.backend.resolve_interpret`: compiled on TPU, interpret elsewhere.

Masking is by *value*: callers mark dead sample slots with ``action =
-1`` and ``log_q = LOG_Q_PAD`` (see `repro.constants`). A row whose
slots are ALL masked produces an exactly-zero gradient row and zero
SNIS weights (not the garbage-scaled output a naive softmax yields).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.backend import resolve_interpret
from repro.constants import LOG_Q_PAD
from repro.kernels.snis_covgrad.backward import (
    snis_covgrad_bwd_pallas,
    snis_covgrad_bwd_tiled_pallas,
)
from repro.kernels.snis_covgrad.kernel import (
    snis_covgrad_fwd_pallas,
    snis_covgrad_fwd_tiled_pallas,
)

DEFAULT_SAMPLE_TILE = 8


def resolve_sample_tile(sample_tile: int, s: int) -> int:
    """The single tile-clamp rule, shared by ops, fopo_loss and the
    trainer: at least 1 (per-sample kernels), never wider than the
    sample count (a wider tile would be pure padding)."""
    return max(1, min(int(sample_tile), s))


def _tile_pad(x: jnp.ndarray, sp: int, fill) -> jnp.ndarray:
    b, s = x.shape
    if sp == s:
        return x
    return jnp.concatenate(
        [x, jnp.full((b, sp - s), fill, x.dtype)], axis=1
    )


def _padded_len(s: int, ts: int) -> int:
    return -(-s // ts) * ts


@functools.partial(jax.jit, static_argnames=("interpret", "sample_tile"))
def snis_covgrad_fused(
    h: jnp.ndarray,  # [B, L] user embeddings
    beta: jnp.ndarray,  # [P, L] fixed item embeddings
    actions: jnp.ndarray,  # [B, S] int32 item ids; -1 marks masked slots
    log_q: jnp.ndarray,  # [B, S]; LOG_Q_PAD on masked slots
    rewards: jnp.ndarray,  # [B, S]
    *,
    interpret: bool | None = None,
    sample_tile: int = DEFAULT_SAMPLE_TILE,
):
    """Fully fused primal op: in-kernel gather + SNIS + covariance grad.

    Returns (grad [B, L], wbar [B, S], scores [B, S]). The SNIS weights
    are recovered from the kernel's sampled scores with one elementwise
    (B, S) softmax — identical math to the kernel's online normaliser —
    then masked to exact zero on dead slots (all-masked rows included).
    """
    interpret = resolve_interpret(interpret)
    s = actions.shape[1]
    h32 = h.astype(jnp.float32)
    beta32 = beta.astype(jnp.float32)
    acts = actions.astype(jnp.int32)
    lq = log_q.astype(jnp.float32)
    rw = rewards.astype(jnp.float32)
    ts = resolve_sample_tile(sample_tile, s)
    if ts > 1:
        sp = _padded_len(s, ts)
        scores, grad = snis_covgrad_fwd_tiled_pallas(
            h32,
            beta32,
            _tile_pad(acts, sp, -1),
            _tile_pad(lq, sp, LOG_Q_PAD),
            _tile_pad(rw, sp, 0.0),
            sample_tile=ts,
            compute_covgrad=True,
            interpret=interpret,
        )
        scores = scores[:, :s]
    else:
        scores, grad = snis_covgrad_fwd_pallas(
            h32, beta32, acts, lq, rw, compute_covgrad=True, interpret=interpret
        )
    wbar = jax.nn.softmax(scores - lq, axis=-1) * (acts >= 0)
    return grad, wbar, scores


@functools.partial(jax.jit, static_argnames=("interpret", "sample_tile"))
def snis_scores_fused(
    h: jnp.ndarray,
    beta: jnp.ndarray,
    actions: jnp.ndarray,
    log_q: jnp.ndarray,
    rewards: jnp.ndarray,
    *,
    interpret: bool | None = None,
    sample_tile: int = DEFAULT_SAMPLE_TILE,
) -> jnp.ndarray:
    """Loss-only forward: sampled scores [B, S] with in-kernel gather,
    skipping the covariance-gradient accumulators (custom_vjp fwd)."""
    interpret = resolve_interpret(interpret)
    s = actions.shape[1]
    h32 = h.astype(jnp.float32)
    beta32 = beta.astype(jnp.float32)
    acts = actions.astype(jnp.int32)
    lq = log_q.astype(jnp.float32)
    rw = rewards.astype(jnp.float32)
    ts = resolve_sample_tile(sample_tile, s)
    if ts > 1:
        sp = _padded_len(s, ts)
        scores = snis_covgrad_fwd_tiled_pallas(
            h32,
            beta32,
            _tile_pad(acts, sp, -1),
            _tile_pad(lq, sp, LOG_Q_PAD),
            _tile_pad(rw, sp, 0.0),
            sample_tile=ts,
            compute_covgrad=False,
            interpret=interpret,
        )
        return scores[:, :s]
    return snis_covgrad_fwd_pallas(
        h32, beta32, acts, lq, rw, compute_covgrad=False, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("interpret", "sample_tile"))
def snis_covgrad_bwd(
    coeff: jnp.ndarray,  # [B, S] per-sample score gradients dL/df
    actions: jnp.ndarray,  # [B, S] int32
    beta: jnp.ndarray,  # [P, L]
    *,
    interpret: bool | None = None,
    sample_tile: int = DEFAULT_SAMPLE_TILE,
) -> jnp.ndarray:
    """grad_h [B, L] = sum_s coeff[b, s] beta[actions[b, s]] — the
    backward gather-reduce (see backward.py)."""
    interpret = resolve_interpret(interpret)
    s = actions.shape[1]
    cf = coeff.astype(jnp.float32)
    acts = actions.astype(jnp.int32)
    beta32 = beta.astype(jnp.float32)
    ts = resolve_sample_tile(sample_tile, s)
    if ts > 1:
        sp = _padded_len(s, ts)
        return snis_covgrad_bwd_tiled_pallas(
            _tile_pad(cf, sp, 0.0),
            _tile_pad(acts, sp, -1),
            beta32,
            sample_tile=ts,
            interpret=interpret,
        )
    return snis_covgrad_bwd_pallas(cf, acts, beta32, interpret=interpret)
