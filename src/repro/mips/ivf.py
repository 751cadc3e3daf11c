"""IVF-Flat MIPS index — the TPU-native replacement for HNSW.

HNSW (the paper's index) is pointer-chasing graph descent: hostile to the
TPU's systolic dataflow. IVF-Flat keeps the paper's *system property* —
training-time retrieval that is strongly sublinear in P and identical to
the serving index — while being two dense matmuls:

  build (once, Assumption 1 fixes beta):
    k-means over items -> C centroids; items bucketed by nearest centroid
    into padded inverted lists [C, cap] (cap = padded max cluster size).
  query:
    (B,L)x(L,C) centroid scores -> top n_probe clusters ->
    gather their lists [B, n_probe*cap] -> gather embeddings ->
    batched dot -> masked top-K.

Cost O(C*L + n_probe*cap*L) ~ O(sqrt(P)*L) per query with C ~ sqrt(P).
Both stages are MXU matmuls; the only gather is the inverted-list fetch.

`ivf_query` below is the pure-jnp query (it materialises the gathered
[B, n_probe*cap, L] candidate tensor in HBM); the kernel-grade query
that streams inverted-list tiles HBM -> VMEM instead lives in
`repro.kernels.ivf_topk` and consumes the same `IVFIndex` — build the
index with ``cap_tile=`` so the padded-list layout is tile-aligned and
the kernel never re-pads. `build_ivf_sharded` builds one local index
per mesh `model` shard (global ids baked in) for the dist retrieval
path (`repro.dist.fopo.dist_ivf_topk`).
"""
from __future__ import annotations

import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.mips.exact import TopK, merge_topk
from repro.mips.streaming import NEG_INF  # noqa: F401  (re-export; kernels import it here)
from repro.obs.trace import current, span


DEFAULT_CAP_TILE = 256
DEFAULT_N_PROBE = 8  # clusters probed per query — one default, every route


def resolve_cap_tile(cap_tile: int | None, cap: int) -> int:
    """THE cap-tile rule, shared by `build_ivf`'s tile-aligned layout
    and the Pallas query wrapper (`repro.kernels.ivf_topk.ops`) so the
    no-repad contract between them cannot drift: clamp to the list
    capacity, then round down to a multiple of 8 — the kernel DMAs
    (CT, L) embedding tiles, whose sublane dim the TPU compiler wants
    8-aligned (interpret mode doesn't care). Widths below 8 pass through
    (toy shapes)."""
    ct = min(cap_tile or DEFAULT_CAP_TILE, cap)
    if ct >= 8:
        ct -= ct % 8
    return ct


class IVFIndex(NamedTuple):
    centroids: jnp.ndarray  # [C, L]
    lists: jnp.ndarray  # [C, cap] int32 item ids, -1 padded
    list_embs: jnp.ndarray  # [C, cap, L] gathered item embeddings (0 padded)
    num_items: int


class ShardedIVFIndex(NamedTuple):
    """One IVF index per mesh `model` shard, stacked on a leading axis
    so shard_map can split it: shard d's lists hold GLOBAL item ids
    (its row-slab offset baked in), so per-shard query results merge
    with the existing id-routing machinery unchanged."""

    centroids: jnp.ndarray  # [n, C, L]
    lists: jnp.ndarray  # [n, C, cap] int32 GLOBAL ids, -1 padded
    list_embs: jnp.ndarray  # [n, C, cap, L]
    num_items: int

    @property
    def n_shards(self) -> int:
        return self.centroids.shape[0]

    def shard(self, d: int) -> IVFIndex:
        return IVFIndex(
            centroids=self.centroids[d],
            lists=self.lists[d],
            list_embs=self.list_embs[d],
            num_items=self.num_items,
        )


# ---------------------------------------------------------------------------
# k-means (Lloyd, fixed iterations, fully jittable)
# ---------------------------------------------------------------------------

def assign_clusters(points: jnp.ndarray, centroids: jnp.ndarray) -> jnp.ndarray:
    """THE L2 nearest-centroid rule: argmin ||x - c||^2 = argmax
    (x.c - ||c||^2/2). Shared by the Lloyd/mini-batch k-means updates,
    the bucketing in `build_ivf`, and the delta-append / compaction
    path in `repro.mips.refresh`, so every maintenance op buckets
    exactly the way the build did. Returns [P] int32."""
    dots = points @ centroids.T  # [P, C]
    c_norm = 0.5 * jnp.sum(centroids**2, axis=-1)  # [C]
    return jnp.argmax(dots - c_norm[None, :], axis=-1).astype(jnp.int32)


def _kmeanspp_init(
    key: jax.Array, points: jnp.ndarray, num_clusters: int
) -> jnp.ndarray:
    """D^2-weighted (k-means++) seeding, fully jittable (scan over C).

    Uniform point seeding leaves ~1/e of well-separated natural
    clusters without a seed; Lloyd iterations can merge but never split,
    so one centroid snowballs the unclaimed mass and the padded-list cap
    — and with it every probe's cost — blows up (observed 16x at
    P ~ 1e5). D^2 weighting puts the next seed in uncovered territory
    with overwhelming probability, which is what keeps the inverted
    lists balanced."""
    p, l = points.shape
    k0, k1 = jax.random.split(key)
    first = points[jax.random.randint(k0, (), 0, p)]
    d2 = jnp.sum((points - first[None, :]) ** 2, axis=-1)  # [P]
    centroids = jnp.zeros((num_clusters, l), points.dtype).at[0].set(first)

    def step(carry, key_i):
        d2, centroids, i = carry
        # categorical over D^2 mass; tiny floor keeps logits finite once
        # every point is within eps of a chosen centroid
        idx = jax.random.categorical(key_i, jnp.log(d2 + 1e-20))
        nxt = points[idx]
        d2 = jnp.minimum(d2, jnp.sum((points - nxt[None, :]) ** 2, axis=-1))
        return (d2, centroids.at[i].set(nxt), i + 1), None

    (_, centroids, _), _ = jax.lax.scan(
        step,
        (d2, centroids, jnp.int32(1)),
        jax.random.split(k1, num_clusters - 1),
    )
    return centroids


def kmeans(
    key: jax.Array,
    points: jnp.ndarray,
    num_clusters: int,
    iters: int = 12,
    *,
    init: str = "kmeans++",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (centroids [C, L], assignment [P] int32). L2 k-means; for MIPS
    we normalise only for clustering, which behaves like spherical k-means.
    ``init`` is "kmeans++" (D^2 seeding — balanced lists on clustered
    catalogs, see `_kmeanspp_init`) or "random" (uniform point seeding)."""
    p, l = points.shape
    if num_clusters > p:
        # jax.random.choice(replace=False) raises past the population size
        warnings.warn(
            f"kmeans: num_clusters={num_clusters} > {p} points; clamping "
            f"to {p} (one cluster per point)",
            stacklevel=2,
        )
        num_clusters = p
    if init == "kmeans++" and num_clusters > 1:
        centroids = _kmeanspp_init(key, points, num_clusters)
    elif init in ("random", "kmeans++"):
        init_idx = jax.random.choice(key, p, (num_clusters,), replace=False)
        centroids = points[init_idx]
    else:
        raise ValueError(f"unknown kmeans init {init!r}")

    def step(centroids, _):
        assign = assign_clusters(points, centroids)  # [P]
        one_hot_sum = jax.ops.segment_sum(points, assign, num_clusters)
        counts = jax.ops.segment_sum(
            jnp.ones((p,), points.dtype), assign, num_clusters
        )
        new_c = one_hot_sum / jnp.maximum(counts[:, None], 1.0)
        # keep empty clusters where they were
        new_c = jnp.where(counts[:, None] > 0, new_c, centroids)
        return new_c, None

    centroids, _ = jax.lax.scan(step, centroids, None, length=iters)
    return centroids, assign_clusters(points, centroids)


# ---------------------------------------------------------------------------
# index build / query
# ---------------------------------------------------------------------------

def bucket_items(
    assign: jnp.ndarray,  # [P] int32 cluster of each item (or C = drop)
    items: jnp.ndarray,  # [P, L]
    num_clusters: int,
    cap: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """THE padded inverted-list bucketing, fully traceable (static
    `num_clusters`/`cap`, zero host syncs): stable-sort items by
    cluster, slot = rank within cluster, scatter into a [C, cap] table
    (-1 padded) + gather the matching [C, cap, L] embeddings.

    Items whose rank overflows `cap` — or whose assignment is the
    out-of-range drop bucket `num_clusters` — are DROPPED from the
    lists (scatter mode="drop"), not clamped: under tracing there is
    nobody to warn. `build_ivf` keeps the eager warn-and-clamp wrapper
    around this; `repro.mips.refresh.compact` counts the drops."""
    p = assign.shape[0]
    counts = jax.ops.segment_sum(
        jnp.ones((p,), jnp.int32), assign, num_clusters + 1
    )
    # stable order: sort items by cluster, then slot = rank within cluster
    order = jnp.argsort(assign, stable=True)
    sorted_assign = assign[order]
    onset = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]]
    )
    rank = jnp.arange(p, dtype=jnp.int32) - onset[sorted_assign]
    lists = jnp.full((num_clusters, cap), -1, jnp.int32)
    lists = lists.at[sorted_assign, rank].set(
        order.astype(jnp.int32), mode="drop"
    )
    safe = jnp.maximum(lists, 0)
    list_embs = jnp.where(
        (lists >= 0)[..., None], jnp.take(items, safe, axis=0), 0.0
    )
    return lists, list_embs


def resolve_cap(cap: int, cap_tile: int | None) -> int:
    """Round a requested list capacity up to the tile the query kernel
    will use (the multiple-of-8 `resolve_cap_tile` rule), so the
    tile-aligned layout contract is decided in one place."""
    if cap_tile is None:
        return cap
    ct = resolve_cap_tile(cap_tile, max(cap, cap_tile))
    return -(-cap // ct) * ct


def build_ivf(
    key: jax.Array,
    items: jnp.ndarray,
    num_clusters: int | None = None,
    cap: int | None = None,
    kmeans_iters: int = 12,
    *,
    cap_tile: int | None = None,
) -> IVFIndex:
    """Cluster + bucket `items` into padded inverted lists.

    ``cap_tile`` rounds the padded list capacity up to a multiple of the
    Pallas query kernel's cap tile, so `repro.kernels.ivf_topk` consumes
    the layout without re-padding (the extra slots are ordinary -1/0
    padding — the jnp query is unaffected).

    Host syncs: with BOTH ``num_clusters`` and ``cap`` passed (static),
    the build is fully traceable — no `.item()` / `int(jnp.max(...))`
    round-trips stalling the device queue, and the whole build jits.
    The price is that the safety rails needing concrete counts are off
    on that path: a cluster overflowing the trusted ``cap`` drops its
    overflow items (rank-clamped scatter) instead of clamping cap up
    with a warning — `repro.mips.refresh.init_refresh_state` counts the
    dropped items in `overflow`, where the health ladder sees them —
    and the degenerate-clustering warning is skipped. Leave ``cap=None``
    (the derive-from-data default) to keep the eager warn-and-clamp
    behaviour.
    """
    with span("index_build"):
        index = _build_ivf(key, items, num_clusters, cap, kmeans_iters, cap_tile)
        if current() is not None and not isinstance(index.lists, jax.core.Tracer):
            # traced runs time the build, not its enqueue; untraced
            # callers keep an asynchronous build
            jax.block_until_ready(index)
    return index


def _build_ivf(key, items, num_clusters, cap, kmeans_iters, cap_tile) -> IVFIndex:
    """`build_ivf`'s body."""
    p, l = items.shape
    if num_clusters is None:
        num_clusters = max(1, int(2 ** round(jnp.log2(jnp.sqrt(p)).item())))
        static = False
    else:
        static = cap is not None
    centroids, assign = kmeans(key, items, num_clusters, kmeans_iters)
    num_clusters = centroids.shape[0]  # kmeans clamps > P (with warning)

    if static:
        # the no-host-sync path: cap is trusted, bucketing fully traced
        lists, list_embs = bucket_items(
            assign, items, num_clusters, resolve_cap(cap, cap_tile)
        )
        return IVFIndex(
            centroids=centroids, lists=lists, list_embs=list_embs, num_items=p
        )

    # derive-from-data path (eager only): size cap off the concrete
    # cluster counts, with the warn-and-clamp safety rails
    counts = jax.ops.segment_sum(
        jnp.ones((p,), jnp.int32), assign, num_clusters
    )
    max_count = int(jnp.max(counts))
    if cap is not None and cap < max_count:
        # honouring the requested cap would silently drop items from the
        # overflowing cluster (mis-bucketing) — clamp up instead
        warnings.warn(
            f"build_ivf: requested cap={cap} < largest cluster "
            f"({max_count} items); clamping cap to {max_count}",
            stacklevel=3,
        )
        cap = max_count
    if cap is None:
        cap = int(2 ** jnp.ceil(jnp.log2(jnp.maximum(max_count, 1))).item())
    cap = resolve_cap(max(cap, max_count), cap_tile)
    if num_clusters > 1 and p >= 256 and max_count > p / 2:
        # (tiny toy catalogs are exempt — every split is lopsided there)
        # one cluster swallowed most of the catalog: every probe of it
        # scans ~P items, so the query degenerates to a dense pass
        warnings.warn(
            f"build_ivf: degenerate clustering — largest cluster holds "
            f"{max_count}/{p} items; queries probing it cost O(P*L)",
            stacklevel=3,
        )
    lists, list_embs = bucket_items(assign, items, num_clusters, cap)
    return IVFIndex(
        centroids=centroids, lists=lists, list_embs=list_embs, num_items=p
    )


def build_ivf_sharded(
    key: jax.Array,
    items: jnp.ndarray,
    n_shards: int,
    num_clusters: int | None = None,
    cap: int | None = None,
    kmeans_iters: int = 12,
    *,
    cap_tile: int | None = None,
) -> ShardedIVFIndex:
    """One IVF index per contiguous row slab of `items` (the same row
    partition `repro.dist` shards beta with), padded to common [C, cap]
    shapes and stacked for shard_map. List ids are GLOBAL (slab offset
    baked in); a ragged tail slab is zero-padded before clustering and
    its pad entries are masked back out of the lists."""
    p, l = items.shape
    rows = -(-p // n_shards)  # ceil: the dist row partition (pad_rows)
    if num_clusters is None:
        num_clusters = max(
            1, int(2 ** round(jnp.log2(jnp.sqrt(rows)).item()))
        )
    num_clusters = min(num_clusters, rows)
    parts = []
    for d in range(n_shards):
        lo = d * rows
        slab = items[lo : min(p, lo + rows)]
        if slab.shape[0] < rows:  # ragged tail: cluster over zero pad rows
            slab = jnp.concatenate(
                [slab, jnp.zeros((rows - slab.shape[0], l), items.dtype)]
            )
        parts.append(
            build_ivf(
                jax.random.fold_in(key, d), slab, num_clusters, cap,
                kmeans_iters, cap_tile=cap_tile,
            )
        )
    cap_max = max(ix.lists.shape[1] for ix in parts)
    if cap_tile is not None:
        ct = resolve_cap_tile(cap_tile, max(cap_max, cap_tile))
        cap_max = -(-cap_max // ct) * ct

    def _pad(ix: IVFIndex, d: int) -> IVFIndex:
        pad = cap_max - ix.lists.shape[1]
        lists = jnp.pad(ix.lists, ((0, 0), (0, pad)), constant_values=-1)
        embs = jnp.pad(ix.list_embs, ((0, 0), (0, pad), (0, 0)))
        gids = jnp.where(lists >= 0, lists + d * rows, -1)
        # mask the ragged-tail pad rows (global id >= P) out of the lists
        dead = gids >= p
        gids = jnp.where(dead, -1, gids).astype(jnp.int32)
        embs = jnp.where(dead[..., None], 0.0, embs)
        return IVFIndex(ix.centroids, gids, embs, num_items=p)

    parts = [_pad(ix, d) for d, ix in enumerate(parts)]
    return ShardedIVFIndex(
        centroids=jnp.stack([ix.centroids for ix in parts]),
        lists=jnp.stack([ix.lists for ix in parts]),
        list_embs=jnp.stack([ix.list_embs for ix in parts]),
        num_items=p,
    )


def ivf_query(
    index: IVFIndex, queries: jnp.ndarray, k: int, n_probe: int = DEFAULT_N_PROBE
) -> TopK:
    """queries [B, L] -> approximate TopK([B, K])."""
    n_probe = min(n_probe, index.centroids.shape[0])
    c_scores = queries @ index.centroids.T  # [B, C]
    _, probe = jax.lax.top_k(c_scores, n_probe)  # [B, n_probe]
    cand_ids = jnp.take(index.lists, probe, axis=0)  # [B, n_probe, cap]
    cand_embs = jnp.take(index.list_embs, probe, axis=0)  # [B, n_probe, cap, L]
    b = queries.shape[0]
    cand_ids = cand_ids.reshape(b, -1)  # [B, n_probe*cap]
    cand_embs = cand_embs.reshape(b, cand_ids.shape[1], -1)
    scores = jnp.einsum("bl,bnl->bn", queries, cand_embs)  # [B, n_probe*cap]
    return merge_topk(scores, cand_ids, k)  # pad slots (-1) back-fill only
