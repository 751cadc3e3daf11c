"""jit'd public wrapper for the tiled IVF query kernel.

`ivf_topk(queries, index, k)` is the kernel-grade twin of
`repro.mips.ivf.ivf_query`: same `IVFIndex`, same TopK result, same
candidate set (identical probe selection), but the inverted-list
gather streams (CT, L) tiles HBM -> VMEM instead of materialising the
[B, n_probe*cap, L] candidate tensor. Stage 1 (centroid scoring + per-
row top-n_probe) runs here as a plain (B, L) x (L, C) matmul — it must
precede the kernel because the probe ids drive the scalar-prefetch
index_maps — and stage 2 is `ivf_topk_pallas`. Beside the probe ids,
stage 1 derives each list's live tile count from the lists themselves
(`live_tiles`), so the kernel neither fetches nor merges the cap tiles
past a list's last occupied slot: retrieval costs what the probed lists
hold, not their padded capacity. The lists are the only record of
occupancy — build, refresh, `delta_append` and compaction keep them
true — so the count needs no field of its own in `IVFIndex`.

Handles n_probe clamping (<= C), padding the list capacity up to the
cap tile (a no-op when the index was built with ``cap_tile=``), and
interpret-mode resolution (None -> the backend rule shared with every
other kernel wrapper; the ExecutionPlan passes its resolved mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import resolve_interpret
from repro.kernels.ivf_topk.kernel import ivf_topk_pallas
from repro.mips.exact import TopK, merge_topk
from repro.mips.ivf import (
    DEFAULT_CAP_TILE,
    DEFAULT_N_PROBE,
    IVFIndex,
    resolve_cap_tile,
)


def tile_align_index(index, cap_tile: int | None):
    """Resolve the cap tile against an index and pad its padded-list
    axis up to a tile multiple ONCE. Returns (aligned index, CT).

    Accepts an `IVFIndex` or a `ShardedIVFIndex` (the list axis is the
    last of `lists`, second-to-last of `list_embs`). Call this at
    retriever/plan construction: the index is static (Assumption 1), so
    leaving a misaligned layout to `_ivf_topk_impl`'s in-trace pad
    fallback would copy the whole [C, cap, L] table in HBM on every
    training step — the exact cost class this kernel exists to remove.
    `build_ivf(..., cap_tile=)` emits the aligned layout up front and
    makes this a no-op."""
    capp = index.lists.shape[-1]
    ct = resolve_cap_tile(cap_tile, capp)
    pad = (-capp) % ct
    if pad:
        wl = [(0, 0)] * index.lists.ndim
        wl[-1] = (0, pad)
        we = [(0, 0)] * index.list_embs.ndim
        we[-2] = (0, pad)
        index = index._replace(
            lists=jnp.pad(index.lists, wl, constant_values=-1),
            list_embs=jnp.pad(index.list_embs, we),
        )
    return index, ct


def live_tiles(lists, cap_tile: int):
    """[..., capp] int32 lists -> [...] int32: the cap tiles of each list
    up to and including the one that holds its LAST occupied slot
    (id >= 0); 0 for an empty list. The last slot, not the count of
    ids: `delta_append` tombstones updated items to -1 in place, and a
    hole must not hide the live slots after it. Takes a traced jax
    array (inside the query) or a concrete numpy one (the host gauge)."""
    slot = np.arange(1, lists.shape[-1] + 1, dtype=np.int32)
    end = ((lists >= 0) * slot).max(axis=-1)  # last occupied slot + 1
    return -(-end // cap_tile)


def live_tile_share(lists, cap_tile: int | None = None) -> float:
    """Live cap tiles over all cap tiles of an index's lists ([..., capp],
    any index layout), under the query's cap-tile rule: the share of a
    uniform probe's kernel grid that does any work. Reads the lists to
    the host — call it where an index is built or rebuilt, never per
    step (the `ivf.live_tile_share` gauge)."""
    lists = np.asarray(lists)
    capp = lists.shape[-1]
    ct = resolve_cap_tile(cap_tile, capp)
    n_tiles = (lists.size // capp) * -(-capp // ct)
    return float(live_tiles(lists, ct).sum()) / n_tiles


def _probe_lists(q, probe, lists, list_embs, *, k, cap_tile, interpret):
    """One kernel pass over one padded-list table (main OR delta) with
    the already-selected probe ids; in-trace tile-align fallback for
    ad-hoc callers (no-op for cap_tile-built or tile_align_index'ed
    layouts — hot paths MUST arrive aligned, or this pad re-copies the
    whole table inside the traced step). The live tile counts come from
    the lists as they are now: one reduction over [C, capp] ids."""
    pad = (-lists.shape[1]) % cap_tile
    if pad:
        lists = jnp.pad(lists, ((0, 0), (0, pad)), constant_values=-1)
        list_embs = jnp.pad(list_embs, ((0, 0), (0, pad), (0, 0)))
    return ivf_topk_pallas(
        q,
        probe,
        live_tiles(lists, cap_tile),
        lists,
        list_embs.astype(jnp.float32),
        k=k,
        cap_tile=cap_tile,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probe", "cap_tile", "delta_cap_tile", "interpret"),
)
def _ivf_topk_impl(
    queries,
    centroids,
    lists,
    list_embs,
    delta_lists=None,
    delta_embs=None,
    *,
    k,
    n_probe,
    cap_tile,
    delta_cap_tile=None,
    interpret,
):
    # stage 1: centroid scores on the MXU + per-row probe selection —
    # computed ONCE; main lists and delta buffers probe the same ids
    q = queries.astype(jnp.float32)
    c_scores = q @ centroids.astype(jnp.float32).T  # [B, C]
    _, probe = jax.lax.top_k(c_scores, n_probe)  # [B, n_probe]
    probe = probe.astype(jnp.int32)

    scores, ids = _probe_lists(
        q, probe, lists, list_embs, k=k, cap_tile=cap_tile,
        interpret=interpret,
    )
    if delta_lists is None:
        return scores, ids

    # delta-buffer probe: the not-yet-compacted appends ride a second
    # (small — dcap << cap) pass of the same kernel, merged via the
    # shared K-merge. Updated items were tombstoned (-1) in the main
    # lists by `delta_append`, so no id appears in both passes.
    d_scores, d_ids = _probe_lists(
        q, probe, delta_lists, delta_embs, k=k, cap_tile=delta_cap_tile,
        interpret=interpret,
    )
    merged = merge_topk(
        jnp.concatenate([scores, d_scores], axis=-1),
        jnp.concatenate([ids, d_ids], axis=-1),
        k,
    )
    return merged.scores, merged.indices


def ivf_topk(
    queries: jnp.ndarray,  # [B, L]
    index: IVFIndex,
    k: int,
    *,
    n_probe: int = DEFAULT_N_PROBE,
    cap_tile: int | None = None,
    interpret: bool | None = None,
    delta: tuple[jnp.ndarray, jnp.ndarray] | None = None,
) -> TopK:
    """queries [B, L] -> approximate TopK([B, K]) over `index`, scored
    by the tiled Pallas kernel. Same candidate set as
    `ivf_query(index, queries, k, n_probe)`.

    ``delta`` is an optional (delta_lists [C, dcap], delta_embs
    [C, dcap, L]) pair — the incremental-maintenance append buffers
    (`repro.mips.refresh.RefreshState.delta()`) — probed alongside the
    main lists with the SAME probe ids and merged into the result."""
    interpret = resolve_interpret(interpret)
    c, capp = index.lists.shape
    n_probe = min(n_probe, c)
    ct = resolve_cap_tile(cap_tile, capp)
    if delta is None:
        scores, ids = _ivf_topk_impl(
            queries,
            index.centroids,
            index.lists,
            index.list_embs,
            k=k,
            n_probe=n_probe,
            cap_tile=ct,
            interpret=interpret,
        )
    else:
        delta_lists, delta_embs = delta
        dct = resolve_cap_tile(cap_tile, delta_lists.shape[1])
        scores, ids = _ivf_topk_impl(
            queries,
            index.centroids,
            index.lists,
            index.list_embs,
            delta_lists,
            delta_embs,
            k=k,
            n_probe=n_probe,
            cap_tile=ct,
            delta_cap_tile=dct,
            interpret=interpret,
        )
    return TopK(scores=scores, indices=ids)
