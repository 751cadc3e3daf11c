"""jit'd public wrapper: GQA-aware flash attention over [B, S, H, dh]
layouts with a full custom VJP (forward kernel saves the per-row
logsumexp; backward runs the dq and dk/dv Pallas kernels). KV heads are
repeated OUTSIDE the custom_vjp so JAX's AD folds the group-sum of
dk/dv back onto the shared heads automatically."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.backend import resolve_interpret
from repro.kernels.flash_attention.backward import flash_backward_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas


def _pad_seq3(x, mult):
    pad = (-x.shape[1]) % mult
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _make_bh_attention(seq_q, seq_kv, causal, window, logit_cap, q_offset,
                       tile_q, tile_kv, interpret):
    """custom_vjp attention over [BH, S, dh] with static config closed over."""

    @jax.custom_vjp
    def attn(q, k, v):
        out, _ = _fwd(q, k, v)
        return out

    def _fwd(q, k, v):
        out, lse = flash_attention_pallas(
            q, k, v, seq_q=seq_q, seq_kv=seq_kv, causal=causal,
            window=window, logit_cap=logit_cap, q_offset=q_offset,
            tile_q=tile_q, tile_kv=tile_kv, interpret=interpret,
        )
        return out, (q, k, v, out, lse)

    def _bwd(res, do):
        q, k, v, out, lse = res
        dsum = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
        dq, dk, dv = flash_backward_pallas(
            q, k, v, do, lse, dsum,
            seq_q=seq_q, seq_kv=seq_kv, causal=causal, window=window,
            logit_cap=logit_cap, q_offset=q_offset,
            tile_q=tile_q, tile_kv=tile_kv, interpret=interpret,
        )
        return dq, dk, dv

    attn.defvjp(_fwd, _bwd)
    return attn


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "logit_cap", "q_offset", "tile_q", "tile_kv",
        "interpret",
    ),
)
def flash_attention(
    q: jnp.ndarray,  # [B, Sq, H, dh]
    k: jnp.ndarray,  # [B, Skv, KV, dh]
    v: jnp.ndarray,  # [B, Skv, KV, dh]
    *,
    causal: bool = True,
    window: int | None = None,
    logit_cap: float | None = None,
    q_offset: int = 0,
    tile_q: int = 512,
    tile_kv: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    interpret = resolve_interpret(interpret)
    b, sq, h, dh = q.shape
    skv, kv = k.shape[1], k.shape[2]
    n_rep = h // kv
    tq = min(tile_q, max(128, 1 << (sq - 1).bit_length()))
    tk = min(tile_kv, max(128, 1 << (skv - 1).bit_length()))
    # [B, S, H, dh] -> [B*H, S, dh]; KV heads shared per group of n_rep
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), n_rep, axis=1).reshape(b * h, skv, dh)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), n_rep, axis=1).reshape(b * h, skv, dh)
    qf = _pad_seq3(qf, tq)
    kf = _pad_seq3(kf, tk)
    vf = _pad_seq3(vf, tk)
    attn = _make_bh_attention(
        sq, skv, causal, window, logit_cap, q_offset, tq, tk, interpret
    )
    out = attn(qf, kf, vf)[:, :sq]
    return out.reshape(b, h, sq, dh).transpose(0, 2, 1, 3)
