"""Gradient compression for cross-pod all-reduce.

At multi-pod scale the `pod`-axis gradient all-reduce crosses the slow
inter-pod links; int8 quantisation with per-tensor scales cuts those
bytes 4x (fp32) / 2x (bf16) at negligible quality cost for DP gradients.
Pattern: quantise -> psum -> dequantise, with an fp32 master copy in the
optimizer (error feedback optional).

These are pure functions designed to wrap a psum inside shard_map /
pjit-lowered code; the dry-run counts their collective bytes, which is
how §Perf measures the win.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def quantize_int8(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compressed_psum(grads: Any, axis_name: str) -> Any:
    """int8 all-reduce over `axis_name`: quantise locally, sum int32
    (exact for <= 2^24 shards), dequantise with the summed scale.
    Call inside shard_map."""

    def one(g):
        q, scale = quantize_int8(g)
        q_sum = jax.lax.psum(q.astype(jnp.int32), axis_name)
        # each shard used its own scale; sum of per-shard maxima is an upper
        # bound — use mean scale for an unbiased-ish reconstruction
        scale_sum = jax.lax.psum(scale, axis_name)
        n = jax.lax.axis_size(axis_name)
        return q_sum.astype(jnp.float32) * (scale_sum / n)

    return jax.tree.map(one, grads)


def error_feedback_compress(grads: Any, residual: Any) -> tuple[Any, Any]:
    """1-bit-SGD-style error feedback: compress (g + e), keep the new
    residual. Returns (quantised (q, scale) tree, new_residual)."""
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(residual)
    qs, new_res = [], []
    for g, e in zip(flat_g, flat_e):
        x = g + e
        q, scale = quantize_int8(x)
        qs.append((q, scale))
        new_res.append(x - dequantize_int8(q, scale))
    return jax.tree.unflatten(treedef, qs), jax.tree.unflatten(treedef, new_res)
