"""Pallas TPU kernel: tiled IVF (inverted-file) top-K MIPS query.

The jnp IVF query (`repro.mips.ivf.ivf_query`) is sublinear in FLOPs
but not in HBM traffic: `jnp.take(list_embs, probe)` materialises the
[B, n_probe*cap, L] candidate-embedding tensor in HBM (written by the
gather, read back by the scoring einsum) on top of the underlying row
reads, and the [B, n_probe*cap] score matrix round-trips too. At paper
shapes that gather tensor alone dwarfs the per-step traffic the fused
covgrad kernels eliminated.

This kernel is the PR-2 gather-tile treatment applied to retrieval:

  grid (B, n_probe, cap/CT), probe ids as a **scalar-prefetch** operand
  (SMEM) so the inverted-list BlockSpec index_maps are data-dependent —
  step (i, jp, jc) DMAs the (CT, L) embedding tile and (1, CT) id tile
  of cluster probe[i, jp] straight HBM -> VMEM (Pallas double-buffers
  the pipeline: the next tile's DMA is in flight while this tile's
  scores contract), scores the tile as ONE (1, L) x (L, CT) MXU
  contraction against the resident query row, and folds it into a
  running masked top-K carried in the output block (the same online
  merge as `repro.kernels.mips_topk`). Neither the [B, n_probe*cap, L]
  candidate tensor nor the [B, n_probe*cap] score matrix ever exists in
  HBM; each live probed tile's bytes move exactly once.

Only the live tiles of a list cost anything. The list capacity is
padded well past the mean list (headroom for appends and compaction),
so most cap tiles of a probed list hold only padding (id -1). A second
scalar-prefetch operand, ``ntiles`` [C], holds each list's live tile
count: the tiles up to and including the one with its LAST occupied
slot (holes that `delta_append` tombstones earlier in a list do not
end it). A step with jc >= ntiles[probe[i, jp]] is dead:

  * its embedding and id index_maps clamp the tile index to the list's
    last live tile, so a dead step names the block the step before it
    fetched and the pipeline issues no DMA;
  * its score and merge run under `pl.when`, so it costs one compare.

The result is exact, bit for bit the same as merging every tile: a
dead tile holds only id -1, which the body masks to NEG_INF, and
merging NEG_INF / -1 lanes placed after the sorted carry leaves the
carry unchanged (the merge is a total order with the carry's lanes
first). The carry's initialisation at (jp, jc) == (0, 0) runs
whatever the tile holds, so a row whose probed lists are all empty
still back-fills NEG_INF / -1.

VMEM per step: q (1, L) + emb tile (CT, L) + id tile (1, CT) + carry
(1, K) x2 + the (1, K+CT) merge — with CT=256, L=128, K=256 (fp32)
~160KB, far inside VMEM with double buffering. CT is a multiple of 8
and the merge runs on the minor axis; Mosaic has no in-kernel top_k or
sort, so the merge is a bitonic network (`_bitonic_sort_desc`);
interpret mode executes the identical body on CPU.

Grid semantics: batch axis parallel; the probe and cap-tile axes are a
sequential reduction into the carry ("arbitrary").

Centroid scoring + per-row top-n_probe happen *before* this kernel (a
(B, L) x (L, C) matmul over the O(sqrt P)-sized centroid table — see
ops.py), and so does the live-tile count: the probe ids and the counts
must exist up front to drive the scalar-prefetch index_maps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.constants import NEG_INF


def _bitonic_sort_desc(s, ids):
    """Sort the (1, N) rows (N a power of 2) by score, descending, with
    ties broken by lower lane first — exactly `lax.top_k`'s order, which
    Mosaic cannot lower in-kernel. A bitonic network: log2(N)(log2(N)+1)/2
    compare-exchange stages, each pairing lane l with lane l ^ j through
    two lane rotations. The original lane rides along as the tie-break
    key, so the order is total and the network exact."""
    n = s.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    pos = lane
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            upper = (lane & j) != 0  # partner is lane - j, else lane + j

            def partner(x, j=j, upper=upper):
                return jnp.where(
                    upper, pltpu.roll(x, j, 1), pltpu.roll(x, n - j, 1)
                )

            ps, pi, pp = partner(s), partner(ids), partner(pos)
            beats = (s > ps) | ((s == ps) & (pos < pp))
            # the lower lane of a forward block keeps the winner
            keep = beats == (upper == ((lane & k) != 0))
            s = jnp.where(keep, s, ps)
            ids = jnp.where(keep, ids, pi)
            pos = jnp.where(keep, pos, pp)
            j //= 2
        k *= 2
    return s, ids


def _ivf_topk_kernel(
    probe_ref,  # [B, n_probe] int32 scalar-prefetch (SMEM)
    ntiles_ref,  # [C] int32 scalar-prefetch (SMEM): live tiles of each list
    q_ref,  # (1, L) query row b (resident across probe/cap steps)
    ids_tile_ref,  # (1, CT) inverted-list ids of cluster probe[b, jp]
    emb_tile_ref,  # (CT, L) that cluster's embedding tile
    scores_ref,  # (1, KP) running top-KP scores (output, accumulated)
    out_ids_ref,  # (1, KP) running top-KP ids (output, accumulated)
):
    i = pl.program_id(0)
    jp = pl.program_id(1)
    jc = pl.program_id(2)

    @pl.when((jp == 0) & (jc == 0))
    def _init():
        scores_ref[...] = jnp.full_like(scores_ref, NEG_INF)
        out_ids_ref[...] = jnp.full_like(out_ids_ref, -1)

    # a tile wholly past its list's last occupied slot holds only dead
    # (-1) ids: merging it would leave the carry as it is
    @pl.when(jc < ntiles_ref[probe_ref[i, jp]])
    def _merge():
        tile = emb_tile_ref[...]  # (CT, L)
        # all CT candidate scores as one contraction against the query row
        s = jax.lax.dot_general(
            q_ref[...], tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (1, CT)
        ids = ids_tile_ref[...]  # (1, CT)
        s = jnp.where(ids >= 0, s, NEG_INF)  # list padding is dead

        # merge: the running top-KP (sorted, so earlier lanes win ties as
        # in lax.top_k over the concatenation) and the tile, padded with
        # dead lanes to a power of two, sorted by the bitonic network
        kp, ct = scores_ref.shape[-1], s.shape[-1]
        n = pl.next_power_of_2(kp + ct)
        parts_s = [scores_ref[...], s]
        parts_i = [out_ids_ref[...], ids]
        if n > kp + ct:
            parts_s.append(jnp.full((1, n - kp - ct), NEG_INF, jnp.float32))
            parts_i.append(jnp.full((1, n - kp - ct), -1, jnp.int32))
        new_s, new_i = _bitonic_sort_desc(
            jnp.concatenate(parts_s, axis=-1),
            jnp.concatenate(parts_i, axis=-1),
        )
        scores_ref[...] = new_s[:, :kp]
        out_ids_ref[...] = new_i[:, :kp]


def ivf_topk_pallas(
    queries: jnp.ndarray,  # [B, L] float32
    probe: jnp.ndarray,  # [B, n_probe] int32 cluster ids (pre-selected)
    ntiles: jnp.ndarray,  # [C] int32 live tiles of each list (`live_tiles`)
    lists: jnp.ndarray,  # [C, capp] int32 item ids, -1 padded; capp % CT == 0
    list_embs: jnp.ndarray,  # [C, capp, L] float32 (0 on padded slots)
    *,
    k: int,
    cap_tile: int,
    interpret: bool = False,
):
    """Returns (scores [B, K], ids [B, K]) — the masked top-K over the
    probed clusters' inverted lists. Rows short of k candidates
    back-fill score NEG_INF / id -1 (the TopK masking convention).

    ``ntiles[c]`` must cover list c's last occupied slot (id >= 0):
    tiles from ntiles[c] on are neither fetched nor merged."""
    b, l = queries.shape
    n_probe = probe.shape[1]
    c, capp = lists.shape
    if capp % cap_tile:
        raise ValueError(
            f"cap={capp} must be padded to a multiple of CT={cap_tile}"
        )
    # the carry is K rounded up to whole 128-lane vregs; its first K
    # lanes are the top-K (same order), cropped on return
    kp = -(-k // 128) * 128
    grid = (b, n_probe, capp // cap_tile)

    def list_tile(i, jp, jc, pr, nt):
        # dead steps re-name the last live tile: same block, no new DMA
        last = jnp.maximum(nt[pr[i, jp]] - 1, 0)
        return pr[i, jp], jnp.minimum(jc, last)

    # Mosaic blocks must match (8, 128) or the whole trailing dims: the
    # per-row operands carry unit dims ([B, 1, L], [C, capp/CT, 1, CT],
    # [B, 1, KP]) so the body still sees (1, L), (1, CT), (1, KP) blocks
    # for any CT (a multiple of 8, for the (CT, L) embedding tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, l), lambda i, jp, jc, pr, nt: (i, 0, 0)),  # query row
            # the data-dependent fetch: which cluster's list/embedding
            # tile to DMA comes from the prefetched probe ids and counts
            pl.BlockSpec(
                (None, None, 1, cap_tile),
                lambda *a: (*list_tile(*a), 0, 0),
            ),
            pl.BlockSpec(
                (None, cap_tile, l), lambda *a: (*list_tile(*a), 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, kp), lambda i, jp, jc, pr, nt: (i, 0, 0)),
            pl.BlockSpec((None, 1, kp), lambda i, jp, jc, pr, nt: (i, 0, 0)),
        ],
    )
    scores, ids = pl.pallas_call(
        _ivf_topk_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, kp), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, kp), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(probe, ntiles, queries.reshape(b, 1, l),
      lists.reshape(c, capp // cap_tile, 1, cap_tile), list_embs)
    return scores[:, 0, :k], ids[:, 0, :k]
