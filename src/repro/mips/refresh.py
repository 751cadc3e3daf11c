"""Incremental IVF index maintenance — the refresh path for drifting beta.

The paper's logarithmic training complexity assumes the MIPS index stays
usable while the item embeddings drift (Assumption 1 only freezes beta
*within* a step). A full `build_ivf` rebuild costs ~30 s at P=131072
against a ~12 ms query, so rebuild-per-refresh turns index freshness into
a stop-the-world cost. This module makes freshness a per-step amortized
cost with three fully-jittable, statically-shaped ops (no host syncs, no
recompiles — every shape is fixed at init):

  `refresh_step`   mini-batch k-means (Sculley 2010): a fixed-size
                   random minibatch of rows nudges its nearest centroids
                   by a per-centroid count-weighted EMA. O(m*C*L) per
                   call vs O(iters*P*C*L) for full Lloyd.
  `delta_append`   new/updated items land in a fixed-capacity per-
                   centroid delta buffer, queried alongside the main
                   lists (see `refresh_query` and the delta probe in
                   `repro.kernels.ivf_topk`). The superseded main/delta
                   slot of an updated item is tombstoned (-1) via the
                   `slot_of` position map, so a stale embedding never
                   shadows its fresh one.
  `compact`        periodic re-bucketing of everything back into the
                   tile-aligned (C, cap) layout the `ivf_topk`
                   BlockSpecs consume, clearing the delta buffers.

All three consume and return a `RefreshState` — a pure-array pytree, so
the trainer can dispatch them asynchronously between steps (JAX's async
dispatch is the "separate stream": the fused FOPO step never blocks on a
refresh; the next step that *uses* the state picks it up through an
ordinary data dependency).

Sharded (`*_sharded`) variants vmap the same ops over the leading shard
axis of `build_ivf_sharded`'s layout: each model shard maintains its own
local lists, ids stay GLOBAL (slab offset baked in), so the dist query
route (`repro.dist.fopo.dist_ivf_topk`) merges them unchanged.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.mips.exact import TopK, merge_topk, recall_at_k, topk_exact
from repro.mips.ivf import (
    DEFAULT_N_PROBE,
    IVFIndex,
    NEG_INF,
    ShardedIVFIndex,
    assign_clusters,
    bucket_items,
    build_ivf,
    build_ivf_sharded,
    resolve_cap,
)


@dataclass(frozen=True)
class RefreshConfig:
    """Index-maintenance schedule, validated by `repro.core.plan`.

    every          refresh the centroids (one mini-batch k-means step)
                   every this many train steps. 0 disables refresh.
    minibatch      rows sampled per refresh step (static — one trace).
    compact_every  full re-bucket (compaction) every this many train
                   steps; also folds the current beta into the lists, so
                   drift between compactions is bounded by this knob.
                   0 disables compaction (delta buffers only).
    delta_cap      per-centroid delta-buffer capacity (static). Appends
                   past it are dropped and counted in `state.overflow`.
    count_decay    per-refresh decay of the k-means EMA counts; < 1.0
                   floors the effective learning rate so centroids keep
                   tracking drift instead of freezing as counts grow.
    """

    every: int = 1
    minibatch: int = 1024
    compact_every: int = 64
    delta_cap: int = 64
    count_decay: float = 0.95


class RefreshState(NamedTuple):
    """The maintained index: main lists + delta buffers + k-means state.

    A pure-array pytree (static shapes everywhere) so the whole
    maintenance cycle jits once and dispatches asynchronously.

    slot_of encodes where each item currently lives, for O(m)
    tombstoning on update:  main slot (c, s)  ->  c*cap + s
                            delta slot (c, s) ->  C*cap + c*delta_cap + s
                            absent            ->  -1
    """

    centroids: jnp.ndarray  # [C, L]
    counts: jnp.ndarray  # [C] f32 — mini-batch k-means EMA weights
    lists: jnp.ndarray  # [C, cap] int32 item ids (GLOBAL), -1 padded
    list_embs: jnp.ndarray  # [C, cap, L] (0 where list slot is -1)
    delta_lists: jnp.ndarray  # [C, dcap] int32 ids, -1 padded
    delta_embs: jnp.ndarray  # [C, dcap, L]
    delta_sizes: jnp.ndarray  # [C] int32 append high-water marks
    slot_of: jnp.ndarray  # [rows] int32 flat slot of each id (see above)
    overflow: jnp.ndarray  # [] int32 — items dropped (cap/delta_cap full)

    @property
    def num_clusters(self) -> int:
        return self.centroids.shape[0]

    @property
    def cap(self) -> int:
        return self.lists.shape[1]

    @property
    def delta_cap(self) -> int:
        return self.delta_lists.shape[1]

    def as_index(self, num_items: int) -> IVFIndex:
        """View the MAIN lists as a query-ready `IVFIndex` (the layout
        the `ivf_topk` kernel consumes; pair with `delta()` to cover
        the not-yet-compacted appends)."""
        return IVFIndex(
            centroids=self.centroids,
            lists=self.lists,
            list_embs=self.list_embs,
            num_items=num_items,
        )

    def delta(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """The (delta_lists, delta_embs) operand pair the query routes
        probe alongside the main lists."""
        return self.delta_lists, self.delta_embs


def _flat_main(c, s, cap, dcap):  # noqa: ARG001 — uniform signature
    return c * cap + s


def _flat_delta(c, s, cap, dcap, num_clusters):
    return num_clusters * cap + c * dcap + s


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_refresh_state(
    index: IVFIndex, rows: int, delta_cap: int, *, id_base: int = 0
) -> RefreshState:
    """Wrap a built `IVFIndex` into a maintainable `RefreshState`.

    `rows` sizes the `slot_of` position map — the id space this state
    may ever see (catalog size; per-shard slab for the sharded route).
    `id_base` shifts GLOBAL list ids into that local [0, rows) range
    (the sharded layout bakes each slab's offset into its ids).

    `overflow` starts at the number of catalog items in this id range
    that the lists do not hold: a fixed-cap `build_ivf` drops a
    cluster's rank overflow, and the health ladder's overflow watch
    must see that loss from the first observation."""
    c, cap = index.lists.shape
    l = index.centroids.shape[1]
    flat = _flat_main(
        jnp.arange(c, dtype=jnp.int32)[:, None],
        jnp.arange(cap, dtype=jnp.int32)[None, :],
        cap, delta_cap,
    )  # [C, cap]
    slot_of = jnp.full((rows,), -1, jnp.int32)
    # dead list slots scatter to the OOB sentinel `rows` and are dropped
    # (-1 would WRAP to the last row — .at[] keeps numpy semantics)
    local = jnp.where(index.lists >= 0, index.lists - id_base, rows)
    slot_of = slot_of.at[local.reshape(-1)].set(
        flat.reshape(-1).astype(jnp.int32), mode="drop"
    )
    occupancy = jnp.sum((index.lists >= 0).astype(jnp.float32), axis=1)
    n_valid = jnp.clip(index.num_items - id_base, 0, rows)
    return RefreshState(
        centroids=index.centroids,
        counts=occupancy,  # seed EMA weights from the build's occupancy
        lists=index.lists,
        list_embs=index.list_embs,
        delta_lists=jnp.full((c, delta_cap), -1, jnp.int32),
        delta_embs=jnp.zeros((c, delta_cap, l), index.list_embs.dtype),
        delta_sizes=jnp.zeros((c,), jnp.int32),
        slot_of=slot_of,
        overflow=(n_valid - jnp.sum(index.lists >= 0)).astype(jnp.int32),
    )


# ---------------------------------------------------------------------------
# mini-batch k-means
# ---------------------------------------------------------------------------

def minibatch_kmeans_step(
    centroids: jnp.ndarray,  # [C, L]
    counts: jnp.ndarray,  # [C] f32 EMA weights
    batch: jnp.ndarray,  # [m, L] sampled rows (mask invalid rows to 0 weight
    weights: jnp.ndarray | None = None,  # [m] f32, optional row mask
    *,
    count_decay: float = 0.95,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One Sculley-style mini-batch k-means update: assign the batch to
    its nearest centroids (the shared `assign_clusters` rule), then move
    each touched centroid toward its batch mean with a count-weighted
    step  c += m_c / (decay*N_c + m_c) * (mean_c - c).  With decay=1
    this is exactly the online k-means 1/N learning rate; decay < 1
    forgets old mass geometrically so the rate floors above zero and
    the centroids keep tracking a drifting distribution."""
    c = centroids.shape[0]
    assign = assign_clusters(batch, centroids)  # [m]
    w = jnp.ones((batch.shape[0],), jnp.float32) if weights is None else weights
    add = jax.ops.segment_sum(batch * w[:, None], assign, c)  # [C, L]
    cnt = jax.ops.segment_sum(w, assign, c)  # [C]
    new_counts = count_decay * counts + cnt
    mean = add / jnp.maximum(cnt, 1.0)[:, None]
    lr = cnt / jnp.maximum(new_counts, 1e-6)  # [C]; 0 where untouched
    new_c = centroids + lr[:, None] * (mean - centroids)
    return new_c, new_counts


def refresh_step(
    state: RefreshState,
    key: jax.Array,
    items: jnp.ndarray,  # [rows, L] the CURRENT embedding table (local slab)
    *,
    minibatch: int,
    count_decay: float = 0.95,
    num_valid: int | None = None,
) -> RefreshState:
    """One centroid refresh: sample `minibatch` rows (with replacement —
    keeps the shape static and the op jittable) and apply one mini-batch
    k-means step. `num_valid` masks a zero-padded ragged tail (sharded
    slabs) out of the update. Lists are untouched — the new centroids
    only change how FUTURE appends/compactions bucket."""
    rows = items.shape[0]
    idx = jax.random.randint(key, (minibatch,), 0, num_valid or rows)
    batch = jnp.take(items, idx, axis=0)
    centroids, counts = minibatch_kmeans_step(
        state.centroids, state.counts, batch, count_decay=count_decay
    )
    return state._replace(centroids=centroids, counts=counts)


# ---------------------------------------------------------------------------
# delta-list appends
# ---------------------------------------------------------------------------

def delta_append(
    state: RefreshState,
    ids: jnp.ndarray,  # [m] int32 LOCAL ids (id_base already subtracted),
    #                    -1 marks an unused slot of the fixed-size batch
    embs: jnp.ndarray,  # [m, L] their fresh embeddings
    *,
    id_base: int = 0,
) -> RefreshState:
    """Append new/updated items to the per-centroid delta buffers.

    Each valid id is assigned to its nearest (current) centroid and
    appended at that centroid's high-water mark; its previous slot
    (main or delta) is tombstoned through `slot_of`, so queries never
    see the stale embedding. Appends past `delta_cap` are dropped and
    counted in `overflow` — compaction (`compact`) folds the full table
    back in, so a drop costs staleness until then, not data loss.
    Stored list ids are GLOBAL (`id_base` re-added) to match the
    sharded layout. Ids must be unique within one call (duplicate ids
    in a batch race on the same slot)."""
    c, cap = state.lists.shape
    dcap = state.delta_cap
    m = ids.shape[0]
    valid = ids >= 0
    safe_ids = jnp.maximum(ids, 0)

    assign = assign_clusters(embs, state.centroids)  # [m]
    # rank of each valid row within its cluster, in batch order:
    # exclusive cumsum over the [m, C] one-hot (m is small — one matmul)
    onehot = (
        jax.nn.one_hot(assign, c, dtype=jnp.int32) * valid[:, None]
    )  # [m, C]
    rank = jnp.cumsum(onehot, axis=0) - onehot  # exclusive
    rank = jnp.sum(rank * onehot, axis=1)  # [m] rank within own cluster
    pos = state.delta_sizes[assign] + rank  # [m] target delta slot
    ok = valid & (pos < dcap)

    # tombstone the superseded slot (main or delta) of every appended id
    old_flat = state.slot_of[safe_ids]  # [m]; -1 = not indexed yet
    flat_lists = jnp.concatenate(
        [state.lists.reshape(-1), state.delta_lists.reshape(-1)]
    )
    dead_idx = jnp.where(ok & (old_flat >= 0), old_flat, flat_lists.shape[0])
    flat_lists = flat_lists.at[dead_idx].set(-1, mode="drop")
    lists = flat_lists[: c * cap].reshape(c, cap)
    delta_lists = flat_lists[c * cap :].reshape(c, dcap)

    # the append itself (scatter with OOB drop where not ok)
    a_idx = jnp.where(ok, assign, c)
    p_idx = jnp.where(ok, pos, dcap)
    delta_lists = delta_lists.at[a_idx, p_idx].set(
        (safe_ids + id_base).astype(jnp.int32), mode="drop"
    )
    delta_embs = state.delta_embs.at[a_idx, p_idx].set(
        embs.astype(state.delta_embs.dtype), mode="drop"
    )
    new_flat = _flat_delta(assign, pos, cap, dcap, c)
    rows = state.slot_of.shape[0]  # OOB sentinel (never -1: .at[] wraps)
    slot_of = state.slot_of.at[jnp.where(ok, safe_ids, rows)].set(
        new_flat.astype(jnp.int32), mode="drop"
    )
    delta_sizes = state.delta_sizes + jax.ops.segment_sum(
        ok.astype(jnp.int32), assign, c
    )
    overflow = state.overflow + jnp.sum(valid & ~ok).astype(jnp.int32)
    return state._replace(
        lists=lists,
        delta_lists=delta_lists,
        delta_embs=delta_embs,
        delta_sizes=jnp.minimum(delta_sizes, dcap),
        slot_of=slot_of,
        overflow=overflow,
    )


# ---------------------------------------------------------------------------
# compaction
# ---------------------------------------------------------------------------

def compact(
    state: RefreshState,
    items: jnp.ndarray,  # [rows, L] the CURRENT embedding table (local slab)
    *,
    id_base: int = 0,
    num_valid: int | None = None,
) -> RefreshState:
    """Re-bucket the FULL table into fresh main lists under the current
    centroids and clear the delta buffers. Embeddings are regathered
    from `items`, so compaction also folds in any drift the delta path
    never saw. Same static (C, cap) tile-aligned layout in and out —
    the `ivf_topk` BlockSpecs never notice. Rows past `num_valid`
    (ragged zero-pad) go to the drop bucket. Rank overflow past `cap`
    is dropped and counted in `overflow` (one more compaction after a
    centroid refresh rebalances it)."""
    c, cap = state.lists.shape
    rows, l = items.shape
    assign = assign_clusters(items, state.centroids)
    if num_valid is not None:  # traced under vmap — no concrete compare
        assign = jnp.where(jnp.arange(rows) < num_valid, assign, c)
    lists, list_embs = bucket_items(assign, items, c, cap)

    flat = _flat_main(
        jnp.arange(c, dtype=jnp.int32)[:, None],
        jnp.arange(cap, dtype=jnp.int32)[None, :],
        cap, state.delta_cap,
    )
    slot_of = jnp.full((rows,), -1, jnp.int32)
    # -1 pad slots -> OOB sentinel (never -1: .at[] wraps) -> dropped
    safe_lists = jnp.where(lists >= 0, lists, rows).reshape(-1)
    slot_of = slot_of.at[safe_lists].set(
        flat.reshape(-1).astype(jnp.int32), mode="drop"
    )
    occupancy = jnp.sum((lists >= 0).astype(jnp.float32), axis=1)
    n_indexed = jnp.sum(occupancy).astype(jnp.int32)
    n_valid = jnp.asarray(
        num_valid if num_valid is not None else rows, jnp.int32
    )
    return RefreshState(
        centroids=state.centroids,
        counts=occupancy,
        lists=jnp.where(lists >= 0, lists + id_base, -1).astype(jnp.int32),
        list_embs=list_embs,
        delta_lists=jnp.full_like(state.delta_lists, -1),
        delta_embs=jnp.zeros_like(state.delta_embs),
        delta_sizes=jnp.zeros_like(state.delta_sizes),
        slot_of=slot_of,
        overflow=n_valid - n_indexed,  # rank-overflow drops this cycle
    )


# ---------------------------------------------------------------------------
# query (pure-jnp reference; the kernel route is repro.kernels.ivf_topk)
# ---------------------------------------------------------------------------

def refresh_query(
    state: RefreshState,
    queries: jnp.ndarray,  # [B, L]
    k: int,
    n_probe: int = DEFAULT_N_PROBE,
    *,
    id_base: int = 0,
) -> TopK:
    """Query main lists AND delta buffers of the probed centroids, merge
    via the shared `merge_topk` (ids are GLOBAL). The jnp reference for
    the kernel route's `delta=` probe."""
    n_probe = min(n_probe, state.num_clusters)
    c_scores = queries @ state.centroids.T  # [B, C]
    _, probe = jax.lax.top_k(c_scores, n_probe)  # [B, n_probe]
    b = queries.shape[0]

    def gather_score(lists, embs):
        ids = jnp.take(lists, probe, axis=0).reshape(b, -1)
        e = jnp.take(embs, probe, axis=0).reshape(b, ids.shape[1], -1)
        return jnp.einsum("bl,bnl->bn", queries, e), ids

    s_main, i_main = gather_score(state.lists, state.list_embs)
    s_delta, i_delta = gather_score(state.delta_lists, state.delta_embs)
    return merge_topk(
        jnp.concatenate([s_main, s_delta], axis=-1),
        jnp.concatenate([i_main, i_delta], axis=-1),
        k,
    )


# ---------------------------------------------------------------------------
# sharded route: one RefreshState per model shard, vmapped ops
# ---------------------------------------------------------------------------

def _shard_id_bases(n_shards: int, rows: int) -> jnp.ndarray:
    return (jnp.arange(n_shards, dtype=jnp.int32) * rows)


def init_refresh_sharded(
    index: ShardedIVFIndex, delta_cap: int
) -> RefreshState:
    """Stacked per-shard states ([n, ...] leading axis on every field)
    from `build_ivf_sharded`'s global-id layout. Use the `*_sharded`
    ops (or shard_map the per-shard ops with in_specs P('model', ...))
    to maintain it."""
    n = index.n_shards
    p = index.num_items
    rows = -(-p // n)  # the dist row partition (ceil)
    bases = _shard_id_bases(n, rows)
    return jax.vmap(
        lambda cent, li, le, base: init_refresh_state(
            IVFIndex(cent, li, le, num_items=p), rows, delta_cap,
            id_base=base,
        )
    )(index.centroids, index.lists, index.list_embs, bases)


def refresh_step_sharded(
    state: RefreshState,  # stacked [n, ...]
    key: jax.Array,
    items: jnp.ndarray,  # [P, L] full (replicated) table
    *,
    minibatch: int,
    count_decay: float = 0.95,
) -> RefreshState:
    """Per-shard mini-batch k-means over each shard's own row slab
    (each shard samples from the rows it indexes; the ragged tail slab
    is masked via num_valid)."""
    n = state.centroids.shape[0]
    p, l = items.shape
    rows = -(-p // n)
    pad = n * rows - p
    if pad:
        items = jnp.concatenate([items, jnp.zeros((pad, l), items.dtype)])
    slabs = items.reshape(n, rows, l)
    valids = jnp.minimum(
        jnp.maximum(p - _shard_id_bases(n, rows), 0), rows
    )  # [n] valid rows per slab

    def one(st, k_, slab, nv):
        idx = jax.random.randint(k_, (minibatch,), 0, jnp.maximum(nv, 1))
        batch = jnp.take(slab, idx, axis=0)
        cent, cnt = minibatch_kmeans_step(
            st.centroids, st.counts, batch, count_decay=count_decay
        )
        return st._replace(centroids=cent, counts=cnt)

    return jax.vmap(one)(state, jax.random.split(key, n), slabs, valids)


def delta_append_sharded(
    state: RefreshState,  # stacked [n, ...]
    ids: jnp.ndarray,  # [m] int32 GLOBAL ids, -1 = unused slot
    embs: jnp.ndarray,  # [m, L]
    num_items: int,
) -> RefreshState:
    """Route each updated item to the shard that owns its row slab
    (ids are global; every shard sees the full batch and keeps only its
    own — the not-mine rows become -1 no-ops, so shapes stay static)."""
    n = state.centroids.shape[0]
    rows = -(-num_items // n)
    bases = _shard_id_bases(n, rows)

    def one(st, base):
        local = ids - base
        mine = (ids >= 0) & (local >= 0) & (local < rows)
        return delta_append(
            st, jnp.where(mine, local, -1), embs, id_base=base
        )

    return jax.vmap(one)(state, bases)


def compact_sharded(
    state: RefreshState,  # stacked [n, ...]
    items: jnp.ndarray,  # [P, L] full (replicated) table
) -> RefreshState:
    """Per-shard compaction over each shard's row slab (global ids)."""
    n = state.centroids.shape[0]
    p, l = items.shape
    rows = -(-p // n)
    pad = n * rows - p
    if pad:
        items = jnp.concatenate([items, jnp.zeros((pad, l), items.dtype)])
    slabs = items.reshape(n, rows, l)
    bases = _shard_id_bases(n, rows)
    valids = jnp.minimum(jnp.maximum(p - bases, 0), rows)
    return jax.vmap(
        lambda st, slab, base, nv: compact(
            st, slab, id_base=base, num_valid=nv
        )
    )(state, slabs, bases, valids)


def sharded_as_index(state: RefreshState, num_items: int) -> ShardedIVFIndex:
    """View stacked per-shard main lists as the `ShardedIVFIndex` the
    dist query route consumes."""
    return ShardedIVFIndex(
        centroids=state.centroids,
        lists=state.lists,
        list_embs=state.list_embs,
        num_items=num_items,
    )


# ---------------------------------------------------------------------------
# health probes + rebuild (the degradation ladder's heavy rungs)
# ---------------------------------------------------------------------------

def sampled_recall(
    state: RefreshState,
    items: jnp.ndarray,  # [P, L] the CURRENT (full) embedding table
    queries: jnp.ndarray,  # [B, L] held probe set
    k: int,
    *,
    n_probe: int = DEFAULT_N_PROBE,
) -> float:
    """Host-side recall@k of the maintained index (main lists + delta
    buffers, `refresh_query`) against exact top-k over `items` on a held
    probe set — the periodic health probe of the retrieval degradation
    ladder (`repro.health.index_health`). Handles both a single state
    and the stacked sharded layout (per-shard probes merged through the
    shared `merge_topk`, ids already GLOBAL)."""
    exact = topk_exact(queries, items, k)
    if state.centroids.ndim == 3:  # stacked [n, ...] sharded state
        per = jax.vmap(
            lambda st: refresh_query(st, queries, k, n_probe)
        )(state)  # TopK with [n, B, k] fields
        b = queries.shape[0]
        approx = merge_topk(
            jnp.moveaxis(per.scores, 0, 1).reshape(b, -1),
            jnp.moveaxis(per.indices, 0, 1).reshape(b, -1),
            k,
        )
    else:
        approx = refresh_query(state, queries, k, n_probe)
    return recall_at_k(approx, exact)


def rebuild(
    state: RefreshState,
    items: jnp.ndarray,  # [rows, L] the CURRENT embedding table (local slab)
    *,
    iters: int = 4,
    id_base: int = 0,
    num_valid: int | None = None,
) -> RefreshState:
    """Full index rebuild, warm-started: `iters` Lloyd iterations over
    the whole table from the CURRENT centroids (no re-seeding — the
    maintained centroids are a better init than k-means++ from scratch,
    and keeping the op jittable rules out the build's host-sync path),
    then a `compact` re-bucket. The ladder's second rung: heals centroid
    drift that a bare compaction (first rung) can't."""
    c = state.num_clusters
    rows = items.shape[0]
    if num_valid is not None:  # traced under vmap — no concrete compare
        w = (jnp.arange(rows) < num_valid).astype(items.dtype)
    else:
        w = jnp.ones((rows,), items.dtype)
    cent = state.centroids
    for _ in range(iters):
        assign = assign_clusters(items, cent)
        add = jax.ops.segment_sum(items * w[:, None], assign, c)
        cnt = jax.ops.segment_sum(w, assign, c)
        # empty clusters keep their centroid (stay available for drift)
        cent = jnp.where(
            cnt[:, None] > 0, add / jnp.maximum(cnt, 1.0)[:, None], cent
        )
    return compact(
        state._replace(centroids=cent),
        items,
        id_base=id_base,
        num_valid=num_valid,
    )


def rebuild_sharded(
    state: RefreshState,  # stacked [n, ...]
    items: jnp.ndarray,  # [P, L] full (replicated) table
    *,
    iters: int = 4,
) -> RefreshState:
    """Per-shard warm rebuild over each shard's row slab (global ids) —
    same slab partition rule as `compact_sharded`."""
    n = state.centroids.shape[0]
    p, l = items.shape
    rows = -(-p // n)
    pad = n * rows - p
    if pad:
        items = jnp.concatenate([items, jnp.zeros((pad, l), items.dtype)])
    slabs = items.reshape(n, rows, l)
    bases = _shard_id_bases(n, rows)
    valids = jnp.minimum(jnp.maximum(p - bases, 0), rows)
    return jax.vmap(
        lambda st, slab, base, nv: rebuild(
            st, slab, iters=iters, id_base=base, num_valid=nv
        )
    )(state, slabs, bases, valids)


# ---------------------------------------------------------------------------
# convenience: build + wrap in one call
# ---------------------------------------------------------------------------

def build_refresh_state(
    key: jax.Array,
    items: jnp.ndarray,
    num_clusters: int,
    cap: int,
    *,
    delta_cap: int = 64,
    kmeans_iters: int = 12,
    cap_tile: int | None = None,
) -> RefreshState:
    """`build_ivf` (static no-host-sync path: both num_clusters and cap
    given) wrapped into a maintainable `RefreshState`."""
    index = build_ivf(
        key, items, num_clusters, cap, kmeans_iters, cap_tile=cap_tile
    )
    return init_refresh_state(index, items.shape[0], delta_cap)


def build_refresh_sharded(
    key: jax.Array,
    items: jnp.ndarray,
    n_shards: int,
    num_clusters: int,
    cap: int,
    *,
    delta_cap: int = 64,
    kmeans_iters: int = 12,
    cap_tile: int | None = None,
) -> RefreshState:
    """Sharded build + wrap (stacked per-shard states, global ids)."""
    index = build_ivf_sharded(
        key, items, n_shards, num_clusters, cap, kmeans_iters,
        cap_tile=cap_tile,
    )
    return init_refresh_sharded(index, delta_cap)


__all__ = [
    "NEG_INF",
    "RefreshConfig",
    "RefreshState",
    "build_refresh_sharded",
    "build_refresh_state",
    "compact",
    "compact_sharded",
    "delta_append",
    "delta_append_sharded",
    "init_refresh_sharded",
    "init_refresh_state",
    "minibatch_kmeans_step",
    "rebuild",
    "rebuild_sharded",
    "refresh_query",
    "refresh_step",
    "refresh_step_sharded",
    "sampled_recall",
    "sharded_as_index",
]
