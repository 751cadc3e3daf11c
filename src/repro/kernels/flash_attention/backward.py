"""Flash attention backward — two Pallas kernels (dq; dk/dv).

Standard FlashAttention-2 formulation with saved per-row logsumexp L and
precomputed D = rowsum(dO * O):

    p  = exp(s - L)
    dv = p^T dO
    dp = dO V^T
    ds = p * (dp - D)
    dq = ds K          (accumulated over kv tiles — dq kernel)
    dk = ds^T Q        (accumulated over q tiles — dkv kernel)

Both kernels re-stream Q/K/V once; the [TQ, TK] tiles never leave VMEM —
the backward HBM traffic matches the forward's O(S*d) instead of the
baseline's O(S^2) logit materialisation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _mask(tq, tk, qi, ki, *, seq_kv, causal, window, q_offset):
    qpos = q_offset + qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    kpos = ki * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    m = kpos < seq_kv
    if causal:
        m &= kpos <= qpos
    if window is not None:
        m &= qpos - kpos < window
    return m


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, dq_ref, acc_ref,
    *, tq, tk, seq_kv, causal, window, logit_cap, scale, q_offset,
):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if logit_cap is not None:
        t = jnp.tanh(s / logit_cap)
        s_capped = logit_cap * t
        dcap = 1.0 - t * t  # d(softcap)/ds
    else:
        s_capped = s
        dcap = None
    mask = _mask(tq, tk, qi, ki, seq_kv=seq_kv, causal=causal,
                 window=window, q_offset=q_offset)
    s_capped = jnp.where(mask, s_capped, NEG_INF)
    p = jnp.exp(s_capped - lse_ref[0][:, None])  # (TQ, TK)
    do = do_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dsum_ref[0][:, None])
    if dcap is not None:
        ds = ds * dcap
    ds = jnp.where(mask, ds, 0.0)
    acc_ref[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, tq, tk, seq_kv, causal, window, logit_cap, scale, q_offset,
):
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if logit_cap is not None:
        t = jnp.tanh(s / logit_cap)
        s_capped = logit_cap * t
        dcap = 1.0 - t * t
    else:
        s_capped = s
        dcap = None
    mask = _mask(tq, tk, qi, ki, seq_kv=seq_kv, causal=causal,
                 window=window, q_offset=q_offset)
    s_capped = jnp.where(mask, s_capped, NEG_INF)
    p = jnp.exp(s_capped - lse_ref[0][:, None])  # (TQ, TK)
    do = do_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    # dv += p^T dO
    dv_acc[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dsum_ref[0][:, None])
    if dcap is not None:
        ds = ds * dcap
    ds = jnp.where(mask, ds, 0.0)
    # dk += ds^T (q*scale)  — scale folded into q already
    dk_acc[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_backward_pallas(
    q, k, v, do, lse, dsum,
    *, seq_q, seq_kv, causal, window, logit_cap, q_offset,
    tile_q=512, tile_kv=512, interpret=False,
):
    bh, sq, dh = q.shape
    skv = k.shape[1]
    scale = 1.0 / float(dh) ** 0.5
    common = dict(tq=tile_q, tk=tile_kv, seq_kv=seq_kv, causal=causal,
                  window=window, logit_cap=logit_cap, scale=scale,
                  q_offset=q_offset)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(bh, sq // tile_q, skv // tile_kv),
        in_specs=[
            pl.BlockSpec((1, tile_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tile_kv, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, tile_kv, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, tile_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tile_q), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, tile_q), lambda b, i, j: (b, i)),
        ],
        out_specs=pl.BlockSpec((1, tile_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((tile_q, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v, do, lse, dsum)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(bh, skv // tile_kv, sq // tile_q),
        in_specs=[
            pl.BlockSpec((1, tile_q, dh), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, tile_kv, dh), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, tile_kv, dh), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, tile_q, dh), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, tile_q), lambda b, j, i: (b, i)),
            pl.BlockSpec((1, tile_q), lambda b, j, i: (b, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile_kv, dh), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, tile_kv, dh), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, skv, dh), k.dtype),
            jax.ShapeDtypeStruct((bh, skv, dh), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_kv, dh), jnp.float32),
            pltpu.VMEM((tile_kv, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v, do, lse, dsum)
    return dq, dk, dv
