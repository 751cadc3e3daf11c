"""Pallas TPU kernel: flash attention (fwd) with causal masking,
sliding-window and Gemma-2 logit soft-cap.

The roofline baseline (EXPERIMENTS.md §Roofline) shows LM train/prefill
cells are MEMORY-bound: XLA materialises every [q_chunk, kv_chunk]
logit tile in HBM between the two attention matmuls — ~60% of the HBM
traffic of a granite-8b train step. This kernel keeps the tile chain
(scores -> mask -> softmax-accumulate -> weighted V) in VMEM: HBM
traffic collapses to one pass over Q/K/V/O blocks.

Grid: (B*H, nq, nkv) — nkv innermost (sequential online-softmax
reduction), (b*h, nq) parallel. Carries (acc, m, l) live in VMEM
scratch; the output block is written at the last kv step.

VMEM per step (TQ=TK=512, dh=128, fp32): q 256KB + k/v 512KB +
scores 1MB + acc 256KB ~ 2MB — double-buffered comfortably.

The kv loop covers the full KV length; causal/window tiles that are
fully masked are cheap (masked to -inf, no branch divergence on the
VPU) — block-level skipping is a further optimisation left on the
table and noted in §Perf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _flash_kernel(
    q_ref,  # (1, TQ, dh)
    k_ref,  # (1, TK, dh)
    v_ref,  # (1, TK, dh)
    o_ref,  # (1, TQ, dh)
    lse_ref,  # (1, TQ) — per-row logsumexp (saved for the backward)
    acc_ref,  # scratch (TQ, dh) f32
    m_ref,  # scratch (TQ, 128) f32 (lane-padded)
    l_ref,  # scratch (TQ, 128) f32
    *,
    tq: int,
    tk: int,
    seq_q: int,
    seq_kv: int,
    causal: bool,
    window: int | None,
    logit_cap: float | None,
    scale: float,
    q_offset: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # (TQ, dh)
    k = k_ref[0].astype(jnp.float32)  # (TK, dh)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (TQ, TK)
    if logit_cap is not None:
        s = logit_cap * jnp.tanh(s / logit_cap)

    qpos = q_offset + qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    kpos = ki * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    mask = kpos < seq_kv
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]  # (TQ, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)  # (TQ, TK)
    corr = jnp.exp(m_prev - m_new)  # (TQ, 1)
    l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (TQ, dh)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, 0] + jnp.log(jnp.maximum(l_ref[:, 0], 1e-30))


def flash_attention_pallas(
    q: jnp.ndarray,  # [BH, Sq, dh] (heads folded into batch, pre-padded)
    k: jnp.ndarray,  # [BH, Skv, dh]
    v: jnp.ndarray,  # [BH, Skv, dh]
    *,
    seq_q: int,
    seq_kv: int,
    causal: bool = True,
    window: int | None = None,
    logit_cap: float | None = None,
    q_offset: int = 0,
    tile_q: int = 512,
    tile_kv: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    bh, sq, dh = q.shape
    skv = k.shape[1]
    assert sq % tile_q == 0 and skv % tile_kv == 0
    grid = (bh, sq // tile_q, skv // tile_kv)
    scale = 1.0 / float(dh) ** 0.5
    kernel = functools.partial(
        _flash_kernel,
        tq=tile_q, tk=tile_kv, seq_q=seq_q, seq_kv=seq_kv,
        causal=causal, window=window, logit_cap=logit_cap,
        scale=scale, q_offset=q_offset,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tile_kv, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, tile_kv, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tile_q), lambda b, i, j: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
            jax.ShapeDtypeStruct((bh, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_q, dh), jnp.float32),
            pltpu.VMEM((tile_q, 128), jnp.float32),
            pltpu.VMEM((tile_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
