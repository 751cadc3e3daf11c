"""Distributed MIPS over a row-sharded catalog.

The item matrix beta [P, L] is sharded over the mesh `model` axis
(P/n_shards rows each). Each shard computes a *local* top-K with any
single-device retriever (streaming blocked top-K by default), then the
[n_shards, B, K] candidates are all-gathered along `model` and reduced to
the global top-K. Communication is O(n_shards * B * K), never O(P).

This is the standard sharded-ANN serving pattern; here it also serves the
*training-time* proposal retrieval, so FOPO training scales to catalogs
that do not fit one device.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.mips.exact import TopK, merge_topk
from repro.mips.streaming import topk_streaming


def merge_topk_along_axis(
    scores: jnp.ndarray,  # [B, K'] local candidate scores
    gids: jnp.ndarray,  # [B, K'] GLOBAL candidate ids, -1 marks dead slots
    k: int,
    axis: str,
) -> TopK:
    """Call INSIDE shard_map: all-gather each shard's [B, K'] candidates
    along `axis` and reduce to the replicated global TopK([B, K]) via
    the shared `merge_topk` (one home for the dead-slot convention: id
    -1 scores NEG_INF and is back-filled when candidates run short) —
    the exact streaming route and the IVF probe route both end here."""
    all_scores = jax.lax.all_gather(scores, axis)  # [n, B, K']
    all_ids = jax.lax.all_gather(gids, axis)
    n, b, local_k = all_scores.shape
    cat_s = jnp.transpose(all_scores, (1, 0, 2)).reshape(b, n * local_k)
    cat_i = jnp.transpose(all_ids, (1, 0, 2)).reshape(b, n * local_k)
    return merge_topk(cat_s, cat_i, k)


def sharded_topk(
    queries: jnp.ndarray,  # [B, L] replicated over `axis`
    items_shard: jnp.ndarray,  # [P/n, L] — local rows (inside shard_map)
    k: int,
    axis: str,
    block_items: int = 4096,
    num_valid: int | None = None,
) -> TopK:
    """Call INSIDE shard_map. Returns replicated global TopK [B, K].

    ``num_valid`` masks the tail of a zero-padded catalog (ragged
    P % n_shards != 0 — see repro.dist.collectives.pad_rows): the local
    top-K is widened by the pad count (pad rows score exactly 0 and
    could otherwise evict a real negative-scoring item from the local
    candidate set before masking), then ids >= num_valid are demoted to
    score NEG_INF / id -1 before the merge — so pad rows never displace
    real items from the global top-K."""
    n = jax.lax.axis_size(axis)
    shard_id = jax.lax.axis_index(axis)
    rows = items_shard.shape[0]
    local_k = k
    if num_valid is not None:
        # widen by the pad count so masking can never cost a real item
        # (topk_streaming back-fills id -1 / NEG_INF past the row count)
        local_k = k + max(0, n * rows - num_valid)
    local = topk_streaming(queries, items_shard, local_k, block_items=block_items)
    # local -> global ids
    gids = jnp.where(
        local.indices >= 0, local.indices + shard_id * rows, -1
    ).astype(jnp.int32)
    if num_valid is not None:
        # demote zero-pad rows (ids >= num_valid) to dead slots pre-merge
        gids = jnp.where(gids < num_valid, gids, -1)
    return merge_topk_along_axis(local.scores, gids, k, axis)


def make_sharded_topk_fn(mesh, k: int, axis: str = "model", block_items: int = 4096):
    """Build a jittable f(queries [B,L], items [P,L]) -> TopK with items
    row-sharded over `axis` and queries/results replicated along it."""

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(axis, None)),
        out_specs=TopK(scores=P(), indices=P()),
        check_vma=False,
    )
    def fn(queries, items_shard):
        return sharded_topk(queries, items_shard, k, axis, block_items)

    return fn


def context_sharded_topk(
    queries: jnp.ndarray,  # [B, L]
    items: jnp.ndarray,  # [P, L]
    k: int,
    *,
    item_axis: str = "model",
    batch_axes=("data",),
    block_items: int = 8192,
    mesh=None,
    num_valid: int | None = None,
) -> TopK:
    """2-D distributed top-K using the AMBIENT mesh (call inside pjit):
    queries row-sharded over `batch_axes`, items row-sharded over
    `item_axis`; each device does a local streaming top-K over its
    (B_loc x P_loc) tile, then merges candidates along `item_axis` only —
    communication O(n_model * B_loc * K), never O(P). This is the §Perf
    replacement for scanning a vocab-sharded table (which broadcasts
    every block)."""

    def fn(q_, it_):
        return sharded_topk(q_, it_, k, item_axis, block_items, num_valid)

    return jax.shard_map(
        fn,
        mesh=mesh,  # None -> the ambient mesh (`with mesh:` context)
        in_specs=(P(batch_axes, None), P(item_axis, None)),
        out_specs=TopK(scores=P(batch_axes, None), indices=P(batch_axes, None)),
        check_vma=False,
    )(queries, items)


def sharded_gather_rows(
    table_shard: jnp.ndarray,  # [V/n, D] local rows (inside shard_map)
    ids: jnp.ndarray,  # [...] global int32 ids, replicated
    axis: str,
) -> jnp.ndarray:
    """Replicated gather from a row-sharded table: mask + local take + psum.
    The workhorse for sharded beta lookups and sharded embedding tables."""
    n = jax.lax.axis_size(axis)
    shard_id = jax.lax.axis_index(axis)
    rows = table_shard.shape[0]
    local_ids = ids - shard_id * rows
    in_shard = (local_ids >= 0) & (local_ids < rows)
    safe = jnp.clip(local_ids, 0, rows - 1)
    vals = jnp.take(table_shard, safe, axis=0)
    vals = jnp.where(in_shard[..., None], vals, 0.0)
    return jax.lax.psum(vals, axis)
