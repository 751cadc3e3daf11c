"""jit'd public wrapper for the in-kernel mixture sampler.

`fused_mixture_sample` turns a jax PRNG key into the kernel's int32
seed operand and returns tile-aligned (actions, log_q, topk_slot) —
each [B, Sp] with Sp = ceil(S/TS)*TS and the padded tail pre-masked
(action = -1, log_q = LOG_Q_PAD). Feeding these straight into the
tiled `snis_covgrad` ops is a no-op pad (Sp % TS == 0 already), which
is the point: step 4 of Algorithm 1 is produced in the layout step 5
consumes.

`interpret=None` takes the backend rule (compiled on TPU, interpret
mode elsewhere). Interpret mode is the CPU fallback: the kernel's PRNG
is a plain-jnp counter hash precisely so the same kernel body runs
under interpret mode (see kernel.py) — there is no separate jnp code
path to drift.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.backend import resolve_interpret
from repro.kernels.fused_sampler.kernel import fused_sampler_pallas


def key_to_seed(key: jax.Array) -> jnp.ndarray:
    """THE key -> int32 kernel-seed fold. One definition so every
    caller (single-device wrapper, dist per-shard sampler, tests)
    derives the identical seed from the same key."""
    return jax.random.randint(
        key, (), 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_samples", "num_items", "sample_tile", "interpret"),
)
def fused_mixture_sample(
    key: jax.Array,
    topk_indices: jnp.ndarray,  # [B, K] int32
    topk_scores: jnp.ndarray,  # [B, K] float32
    *,
    num_samples: int,
    epsilon,  # float or traced jnp scalar, 0 <= eps < 1
    num_items: int,
    sample_tile: int,
    interpret: bool | None = None,
    row_offset: int | jnp.ndarray = 0,
):
    """Draw S eps-mixture actions per context in-kernel; returns
    (actions [B, Sp], log_q [B, Sp], topk_slot [B, Sp]). ``row_offset``
    shifts the counter hash's batch-row key: a batch shard holding
    global rows [o, o + B) passes o and draws exactly those rows of
    the full-batch stream (how the dist path keeps per-shard streams
    disjoint AND mesh-shape-reproducible)."""
    interpret = resolve_interpret(interpret)
    # fold the jax key into the kernel's counter-hash seed; consuming
    # the key here keeps the usual "split per step" discipline upstream
    seed = key_to_seed(key)
    return fused_sampler_pallas(
        seed,
        jnp.asarray(epsilon, jnp.float32),
        topk_indices,
        topk_scores,
        num_samples=num_samples,
        num_items=num_items,
        sample_tile=sample_tile,
        interpret=interpret,
        row_offset=row_offset,
    )
