"""jit'd public wrapper for the EmbeddingBag Pallas kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.backend import resolve_interpret
from repro.kernels.embedding_bag.kernel import embedding_bag_pallas
from repro.kernels.embedding_bag.ref import embedding_bag_ref


@functools.partial(jax.jit, static_argnames=("combiner", "interpret"))
def embedding_bag(
    table: jnp.ndarray,  # [V, D]
    indices: jnp.ndarray,  # [B, T] int32, -1 padded
    combiner: str = "sum",
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    interpret = resolve_interpret(interpret)
    if combiner == "max":  # documented fallback: gather is the hot path
        return embedding_bag_ref(table, indices, combiner="max")
    out = embedding_bag_pallas(table, indices.astype(jnp.int32), interpret=interpret)
    if combiner == "mean":
        counts = jnp.sum((indices >= 0).astype(table.dtype), axis=1, keepdims=True)
        out = out / jnp.maximum(counts, 1e-9)
    return out
