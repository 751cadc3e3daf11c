"""Pallas TPU kernel: streaming blocked top-K maximum-inner-product search.

The hot spot of both the dense retrieval path and the REINFORCE baseline
is `scores = H @ Beta^T` followed by top-K — naively an O(B*P) HBM
intermediate. This kernel streams the catalog through VMEM in blocks of
`block_items`, scoring each block on the MXU and folding it into a
running top-K carried in the output block (flash-attention-style online
reduction). The (B, P) score matrix never exists; Beta is read from HBM
exactly once.

Grid: (B_tiles, P_blocks) with the catalog axis innermost ("arbitrary"
semantics — it is a sequential reduction; the batch axis is parallel).
VMEM working set per step:
    queries  (TB, L)    + items (BP, L)    + scores (TB, BP)
    + carry  (TB, K) x2
With TB=128, BP=1024, L=128, K=256 (fp32): 64KB + 512KB + 512KB + 256KB
≈ 1.3MB — comfortably inside the ~16MB v5e VMEM with double buffering.
TB and BP are multiples of 128 / 8 so the matmul hits MXU-native tiling.

The in-kernel merge uses jax.lax.top_k on the concatenated
(TB, K + BP) candidates (Mosaic lowers sort/top_k on the minor axis;
interpret mode executes it directly on CPU for validation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.constants import NEG_INF  # python scalar: jnp consts would be captured


def _mips_topk_kernel(
    q_ref,  # (TB, L) queries tile
    items_ref,  # (BP, L) catalog block
    scores_ref,  # (TB, K) running top-K scores  (output, accumulated)
    ids_ref,  # (TB, K) running top-K ids      (output, accumulated)
    *,
    k: int,
    block_items: int,
    num_items: int,
):
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        scores_ref[...] = jnp.full_like(scores_ref, NEG_INF)
        ids_ref[...] = jnp.full_like(ids_ref, -1)

    q = q_ref[...]
    blk = items_ref[...]
    # (TB, BP) block scores on the MXU, fp32 accumulation
    s = jax.lax.dot_general(
        q, blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    base = p * block_items
    ids = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(ids < num_items, s, NEG_INF)

    cat_s = jnp.concatenate([scores_ref[...], s], axis=-1)  # (TB, K+BP)
    cat_i = jnp.concatenate([ids_ref[...], ids], axis=-1)
    new_s, pos = jax.lax.top_k(cat_s, k)
    scores_ref[...] = new_s
    ids_ref[...] = jnp.take_along_axis(cat_i, pos, axis=-1)


def mips_topk_pallas(
    queries: jnp.ndarray,  # [B, L] (pre-padded: B % tb == 0, L untouched)
    items: jnp.ndarray,  # [Pp, L] (pre-padded: Pp % block_items == 0)
    *,
    k: int,
    num_items: int,  # true P before padding (for masking)
    tile_batch: int = 128,
    block_items: int = 1024,
    interpret: bool = False,
):
    b, l = queries.shape
    pp = items.shape[0]
    assert b % tile_batch == 0 and pp % block_items == 0
    grid = (b // tile_batch, pp // block_items)
    kernel = functools.partial(
        _mips_topk_kernel, k=k, block_items=block_items, num_items=num_items
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_batch, l), lambda i, p: (i, 0)),
            pl.BlockSpec((block_items, l), lambda i, p: (p, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_batch, k), lambda i, p: (i, 0)),
            pl.BlockSpec((tile_batch, k), lambda i, p: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(queries, items)
