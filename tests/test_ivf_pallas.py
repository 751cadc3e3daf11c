"""The tiled Pallas IVF query kernel (`repro.kernels.ivf_topk`):
kernel-vs-ref parity, recall against the exact oracle, ragged-cluster /
padded-cap properties, ExecutionPlan wiring, and loss/grad parity of
`retriever="ivf_pallas"` against the exact-retriever fused step on
identical retrieved sets."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ExecutionPlan, FOPOConfig, fopo_loss
from repro.core.policy import SoftmaxPolicy, linear_tower_apply, linear_tower_init
from repro.core.rewards import make_session_reward
from repro.data import clustered_catalog
from repro.kernels.ivf_topk import ivf_topk, ivf_topk_ref, live_tile_share, live_tiles
from repro.mips import build_ivf, build_ivf_sharded, ivf_query, recall_at_k, topk_exact
from repro.mips.ivf import IVFIndex


# ---------------------------------------------------------------------------
# kernel vs jnp ref — one candidate set, element-for-element
# ---------------------------------------------------------------------------

def _case(*args, layout="built"):
    """A parity case (p, l, c, b, k, n_probe, cap_tile) over an index of
    the given layout; the built-index cases keep their plain ids."""
    name = "-".join(map(str, args))
    return pytest.param(
        *args, layout, id=name if layout == "built" else f"{layout}-{name}"
    )


def _index_of_lengths(items, lengths, cap, key):
    """An IVFIndex whose list c holds lengths[c] distinct items at its
    head (the rest -1 / 0 padding) under random centroids."""
    lists = np.full((len(lengths), cap), -1, np.int32)
    start = np.cumsum([0, *lengths[:-1]])
    for c, (s0, n) in enumerate(zip(start, lengths)):
        lists[c, :n] = np.arange(s0, s0 + n)
    embs = np.where(
        (lists >= 0)[..., None], np.asarray(items)[np.maximum(lists, 0)], 0.0
    )
    centroids = jax.random.normal(key, (len(lengths), items.shape[1]))
    return IVFIndex(centroids, jnp.asarray(lists),
                    jnp.asarray(embs, jnp.float32), items.shape[0])


def _parity_inputs(layout, p, l, c, b, k, n_probe, cap_tile):
    """(index, queries, delta, reference TopK) of one parity case.

    built        the k-means index as `build_ivf` lays it out
    mixed        lists of 0 (two), 1, 3, CT + 1, 2 CT, cap - 1 items and
                 one full to the cap
    empty_probe  as mixed, with row 0's probed lists all empty: it
                 back-fills NEG_INF / -1 from the carry's initialisation
    holes        `delta_append` tombstoned the first two tiles of the
                 longest list: its live slots lie after the hole
    delta        a few appends in the (mostly empty) delta buffers,
                 probed beside the main lists"""
    from repro.mips.refresh import delta_append, init_refresh_state, refresh_query

    kq, ki = jax.random.split(jax.random.PRNGKey(p + k))
    items = jax.random.normal(ki, (p, l))
    q = jax.random.normal(kq, (b, l))
    if layout in ("mixed", "empty_probe"):
        ct, cap = cap_tile, 6 * cap_tile
        lengths = [0, cap, 1, ct + 1, 0, 2 * ct, cap - 1, 3]
        assert len(lengths) == c and sum(lengths) <= p
        index = _index_of_lengths(items, lengths, cap, jax.random.PRNGKey(3))
        if layout == "empty_probe":
            # row 0 scores the two empty lists (0 and 4) above the rest
            cent = index.centroids.at[0].set(100 * q[0]).at[4].set(99 * q[0])
            index = index._replace(centroids=cent)
        return index, q, None, ivf_topk_ref(q, index, k, n_probe=n_probe)
    index = build_ivf(jax.random.PRNGKey(3), items, num_clusters=c, kmeans_iters=6)
    if layout == "built":
        return index, q, None, ivf_topk_ref(q, index, k, n_probe=n_probe)
    lists = np.asarray(index.lists)
    if layout == "holes":
        longest = int(np.argmax((lists >= 0).sum(1)))
        moved = lists[longest, : 2 * cap_tile]
        assert (moved >= 0).all() and (lists[longest, 2 * cap_tile:] >= 0).any()
        state = init_refresh_state(index, p, delta_cap=2 * cap_tile)
    else:  # delta: re-embed three items of the first probed rows' lists
        moved = lists[lists >= 0][:3]
        state = init_refresh_state(index, p, delta_cap=cap_tile)
    state = delta_append(state, jnp.asarray(moved), items[moved] * 1.5)
    if layout == "holes":
        assert (np.asarray(state.lists)[longest, : 2 * cap_tile] == -1).all()
        index = state.as_index(p)
        return index, q, None, ivf_topk_ref(q, index, k, n_probe=n_probe)
    return (state.as_index(p), q, state.delta(),
            refresh_query(state, q, k, n_probe))


@pytest.mark.parametrize(
    "p,l,c,b,k,n_probe,cap_tile,layout",
    [
        _case(500, 16, 8, 4, 16, 3, 8),     # ragged clusters, CT | cap
        _case(777, 8, 16, 5, 32, 8, 16),    # odd P
        _case(256, 32, 4, 3, 8, 2, 128),    # CT > cap -> clamped to cap
        _case(300, 16, 8, 4, 16, 5, 7),     # CT does not divide cap -> pad path
        _case(64, 8, 64, 2, 8, 64, 8),      # one item per cluster (C == P region)
        _case(200, 16, 8, 4, 128, 8, 8, layout="mixed"),
        _case(200, 16, 8, 3, 16, 2, 8, layout="empty_probe"),
        _case(600, 16, 8, 4, 128, 8, 8, layout="holes"),
        _case(500, 16, 8, 4, 32, 3, 8, layout="delta"),
    ],
)
def test_ivf_topk_matches_ref(p, l, c, b, k, n_probe, cap_tile, layout):
    """The kernel returns the reference's ids exactly, in order, and its
    scores to float32 rounding — whatever share of each probed list's
    cap tiles is live (the dead ones are skipped)."""
    index, q, delta, ref = _parity_inputs(layout, p, l, c, b, k, n_probe, cap_tile)
    out = ivf_topk(q, index, k, n_probe=n_probe, cap_tile=cap_tile,
                   interpret=True, delta=delta)
    np.testing.assert_allclose(
        np.asarray(out.scores), np.asarray(ref.scores), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(out.indices), np.asarray(ref.indices))
    if layout == "empty_probe":
        assert (np.asarray(out.indices)[0] == -1).all()
        assert (np.asarray(out.scores)[0] < -1e37).all()


def test_live_tiles_end_at_the_last_occupied_slot():
    """A list's live tiles run to the tile of its LAST id >= 0: a hole
    does not end the list, an empty list has none; the gauge's share
    counts them over all tiles, under the query's cap-tile rule."""
    lists = np.array([
        [-1, -1, -1, -1, -1, -1, -1, -1],  # empty
        [0, 1, 2, -1, -1, -1, -1, -1],  # head only
        [-1, -1, -1, -1, -1, 5, -1, -1],  # live slot after a hole
        [3, -1, -1, -1, -1, -1, -1, 4],  # holes, last slot live
        [6, 7, 8, 9, 10, 11, 12, 13],  # full to the cap
    ], np.int32)
    want = [0, 2, 3, 4, 4]
    np.testing.assert_array_equal(live_tiles(lists, 2), want)
    traced = jax.jit(lambda x: live_tiles(x, 2))(jnp.asarray(lists))
    np.testing.assert_array_equal(np.asarray(traced), want)
    assert live_tile_share(lists, 2) == sum(want) / 20
    assert live_tile_share(jnp.asarray(lists), 3) == 9 / 15  # CT 3 pads to 9
    assert live_tile_share(lists[None]) == 4 / 5  # CT clamps to the cap


def test_ivf_topk_exhaustive_probe_equals_exact():
    """Probing every cluster makes the candidate set the whole catalog:
    the kernel must reproduce the exact dense top-K."""
    kq, ki = jax.random.split(jax.random.PRNGKey(0))
    items = jax.random.normal(ki, (512, 16))
    q = jax.random.normal(kq, (6, 16))
    index = build_ivf(jax.random.PRNGKey(1), items, num_clusters=16, cap_tile=16)
    out = ivf_topk(q, index, 48, n_probe=16, cap_tile=16, interpret=True)
    ref = topk_exact(q, items, 48)
    np.testing.assert_allclose(
        np.asarray(out.scores), np.asarray(ref.scores), rtol=1e-5
    )
    assert (
        np.sort(np.asarray(out.indices), -1) == np.sort(np.asarray(ref.indices), -1)
    ).all()


def test_ivf_topk_short_candidates_backfill():
    """k beyond the probed candidate count back-fills id -1 / NEG_INF —
    the masked-TopK convention the proposal layer already consumes."""
    items = jax.random.normal(jax.random.PRNGKey(0), (100, 8))
    q = jax.random.normal(jax.random.PRNGKey(1), (3, 8))
    index = build_ivf(jax.random.PRNGKey(2), items, num_clusters=8)
    out = ivf_topk(q, index, 96, n_probe=1, interpret=True)
    ids = np.asarray(out.indices)
    scores = np.asarray(out.scores)
    assert (ids[:, -1] == -1).all()  # one cluster can't hold 96 items
    assert (scores[:, -1] < -1e37).all()
    # filled prefix is valid and duplicate-free
    for row_ids in ids:
        real = row_ids[row_ids >= 0]
        assert len(set(real.tolist())) == len(real)
        assert (real < 100).all()


# ---------------------------------------------------------------------------
# recall regression — jnp and Pallas paths against the exact oracle
# ---------------------------------------------------------------------------

def test_ivf_recall_regression():
    """Seeded clustered catalog: recall@K >= 0.95 for BOTH query paths
    at a fixed (P, C, n_probe) — the guard on the sublinear route's
    quality (kmeans++ list balance is what keeps this cheap)."""
    p, l, c, b, k, n_probe = 4096, 16, 64, 8, 32, 4
    items, queries = map(jnp.asarray, clustered_catalog(p, l, c, b, seed=7))
    index = build_ivf(
        jax.random.PRNGKey(1), items, num_clusters=c, kmeans_iters=6,
        cap_tile=32,
    )
    exact = topk_exact(queries, items, k)
    rec_jnp = recall_at_k(ivf_query(index, queries, k, n_probe=n_probe), exact)
    rec_pal = recall_at_k(
        ivf_topk(queries, index, k, n_probe=n_probe, cap_tile=32, interpret=True),
        exact,
    )
    assert rec_jnp >= 0.95, rec_jnp
    assert rec_pal >= 0.95, rec_pal


def test_ivf_ragged_padded_cap_properties():
    """Property sweep over skewed (ragged) cluster geometries and
    non-dividing cap tiles: every returned id is valid or -1, rows are
    duplicate-free, scores are descending, and every real id came from
    a probed cluster."""
    for seed in range(4):
        kk = jax.random.split(jax.random.PRNGKey(seed), 4)
        p = int(jax.random.randint(kk[0], (), 150, 900))
        c = int(jax.random.randint(kk[1], (), 3, 24))
        # skewed catalog: half the items piled near one center
        items = jax.random.normal(kk[2], (p, 12))
        items = items.at[: p // 2].mul(0.05)
        q = jax.random.normal(kk[3], (5, 12))
        # cap=None: the derive-from-data path sizes cap off the actual
        # (skewed) cluster counts — the ragged geometry under test
        index = build_ivf(
            jax.random.PRNGKey(seed + 100), items, num_clusters=c,
            cap=None, kmeans_iters=4,
        )
        cap = index.lists.shape[1]
        lists = np.asarray(index.lists)
        assert sorted(lists[lists >= 0].tolist()) == list(range(p))
        k, n_probe, ct = 24, 2, 7  # ct=7 never divides cap cleanly
        out = ivf_topk(q, index, k, n_probe=n_probe, cap_tile=min(ct, cap),
                       interpret=True)
        scores, ids = np.asarray(out.scores), np.asarray(out.indices)
        assert ((ids >= -1) & (ids < p)).all()
        for i in range(ids.shape[0]):
            real = ids[i][ids[i] >= 0]
            assert len(set(real.tolist())) == len(real)
        assert (np.diff(scores, axis=-1) <= 1e-6).all()  # descending
        # provenance: real ids all belong to the probed clusters
        c_scores = np.asarray(q @ index.centroids.T)
        probe = np.argsort(-c_scores, -1)[:, : min(n_probe, c)]
        for i in range(ids.shape[0]):
            allowed = set(lists[probe[i]].ravel().tolist())
            assert set(ids[i][ids[i] >= 0].tolist()) <= allowed


# ---------------------------------------------------------------------------
# plan wiring + fused-step parity
# ---------------------------------------------------------------------------

def _fopo_problem(seed=0, b=4, l=12, p=160):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    beta = jax.random.normal(ks[0], (p, l))
    x = jax.random.normal(ks[1], (b, l))
    params = linear_tower_init(ks[2], l, l)
    policy = SoftmaxPolicy(tower=linear_tower_apply, item_dim=l)
    positives = jax.random.randint(ks[3], (b, 6), 0, p, dtype=jnp.int32)
    return policy, params, x, beta, make_session_reward(positives)


def test_plan_validates_ivf_pallas():
    with pytest.raises(ValueError, match="index"):
        ExecutionPlan.resolve(FOPOConfig(num_items=10, retriever="ivf_pallas"))
    beta = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
    shards = build_ivf_sharded(jax.random.PRNGKey(1), beta, 2, num_clusters=4)
    with pytest.raises(ValueError, match="IVFIndex"):
        # a sharded index on the single-device path is a config bug
        ExecutionPlan.resolve(
            FOPOConfig(num_items=64, retriever="ivf_pallas"),
            retriever_kwargs={"index": shards},
        )


def test_plan_validates_ivf_pallas_under_dist():
    from repro.dist.fopo import make_debug_dist

    dist = make_debug_dist(1, 1)
    beta = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
    cfg = FOPOConfig(num_items=64, retriever="ivf_pallas", dist=dist)
    with pytest.raises(ValueError, match="build_ivf_sharded"):
        ExecutionPlan.resolve(cfg)
    with pytest.raises(ValueError, match="build_ivf_sharded"):
        # a plain (unsharded) index under dist= is a config bug
        ExecutionPlan.resolve(
            cfg,
            retriever_kwargs={
                "index": build_ivf(jax.random.PRNGKey(1), beta, 4)
            },
        )
    with pytest.raises(ValueError, match="model axis is 1"):
        ExecutionPlan.resolve(
            cfg,
            retriever_kwargs={
                "index": build_ivf_sharded(
                    jax.random.PRNGKey(1), beta, 2, num_clusters=4
                )
            },
        )


def test_fused_step_parity_exact_vs_ivf_pallas():
    """Acceptance gate: with exhaustive probes the ivf_pallas retriever
    returns the exact retrieved set, so the fused step's loss and grads
    must match the exact-retriever fused step to <= 1e-5 rel."""
    policy, params, x, beta, reward_fn = _fopo_problem(seed=3, p=160)
    index = build_ivf(jax.random.PRNGKey(9), beta, num_clusters=8, cap_tile=16)
    kwargs = {"index": index, "n_probe": 8, "cap_tile": 16}
    base = dict(
        num_items=160, num_samples=33, top_k=16, epsilon=0.5,
        fused=True, fused_interpret=True, sample_tile=8,
    )
    cfg_ivf = FOPOConfig(retriever="ivf_pallas", **base)
    cfg_ex = FOPOConfig(retriever="exact", **base)
    key = jax.random.PRNGKey(5)
    plan = ExecutionPlan.resolve(cfg_ivf, retriever_kwargs=kwargs)

    l1, _ = plan.execute(policy, params, key, x, beta, reward_fn)
    l2, _ = fopo_loss(policy, params, key, x, beta, reward_fn, cfg_ex)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)

    g1 = jax.grad(
        lambda pp: plan.execute(policy, pp, key, x, beta, reward_fn)[0]
    )(params)
    g2 = jax.grad(
        lambda pp: fopo_loss(policy, pp, key, x, beta, reward_fn, cfg_ex)[0]
    )(params)
    np.testing.assert_allclose(
        np.asarray(g1["w"]), np.asarray(g2["w"]), rtol=1e-5, atol=1e-7
    )


def test_trainer_ivf_pallas_end_to_end():
    """FOPOTrainer wires retriever="ivf_pallas" through the plan and
    trains (loss finite, eval improves over init is covered by the
    system sweep for the other retrievers — here we check the wiring)."""
    from repro.data import SyntheticConfig, generate_sessions
    from repro.train import FOPOTrainer, TrainerConfig

    ds = generate_sessions(
        SyntheticConfig(num_items=120, num_users=32, embed_dim=8,
                        session_len=4, seed=0)
    )
    index = build_ivf(
        jax.random.PRNGKey(0), jnp.asarray(ds.item_embeddings),
        num_clusters=8, cap_tile=16,
    )
    fopo = FOPOConfig(
        num_items=0, num_samples=16, top_k=8, retriever="ivf_pallas",
        fused=True, fused_interpret=True, sample_tile=8,
    )
    tr = FOPOTrainer(
        TrainerConfig(estimator="fopo", fopo=fopo, batch_size=8,
                      num_steps=4, checkpoint_every=0),
        ds,
        retriever_kwargs={"index": index, "n_probe": 4, "cap_tile": 16},
    )
    hist = tr.train(4)
    assert np.isfinite(hist["loss"]).all()


# ---------------------------------------------------------------------------
# dist: per-shard local-list probing + K-merge (multi-device subprocess)
# ---------------------------------------------------------------------------

def test_dist_ivf_pallas_multidevice():
    import os
    import subprocess
    import sys

    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core import ExecutionPlan, FOPOConfig, fopo_loss
from repro.core.policy import SoftmaxPolicy, linear_tower_apply, linear_tower_init
from repro.core.rewards import make_session_reward
from repro.dist.fopo import dist_ivf_topk, make_debug_dist
from repro.mips import build_ivf_sharded, topk_exact

dist = make_debug_dist(2, 2)
kq, ki = jax.random.split(jax.random.PRNGKey(0))
q = jax.random.normal(kq, (8, 16))
items = jax.random.normal(ki, (777, 16))  # ragged: 777 over 4... 2 shards
shards = build_ivf_sharded(jax.random.PRNGKey(2), items, 2, num_clusters=16, cap_tile=16)
from repro.kernels.ivf_topk import live_tile_share
assert live_tile_share(shards.lists, 16) < 0.9  # each shard skips dead tiles
out = dist_ivf_topk(q, shards, 32, dist, n_probe=16, cap_tile=16, interpret=True)
ref = topk_exact(q, items, 32)
np.testing.assert_allclose(np.asarray(out.scores), np.asarray(ref.scores), rtol=1e-5)
assert (np.sort(np.asarray(out.indices), -1) == np.sort(np.asarray(ref.indices), -1)).all()

# end-to-end: dist x ivf_pallas (+ fused sampler) == single-device exact
ks = jax.random.split(jax.random.PRNGKey(1), 4)
p, l, b = 160, 12, 4
beta = jax.random.normal(ks[0], (p, l))
x = jax.random.normal(ks[1], (b, l))
params = linear_tower_init(ks[2], l, l)
policy = SoftmaxPolicy(tower=linear_tower_apply, item_dim=l)
reward_fn = make_session_reward(jax.random.randint(ks[3], (b, 6), 0, p, dtype=jnp.int32))
key = jax.random.PRNGKey(5)
sh = build_ivf_sharded(jax.random.PRNGKey(9), beta, 2, num_clusters=8, cap_tile=16)
cfg_d = FOPOConfig(num_items=p, num_samples=33, top_k=16, epsilon=0.5,
                   retriever="ivf_pallas", fused_sampler=True,
                   fused_interpret=True, sample_tile=8, dist=dist)
plan = ExecutionPlan.resolve(cfg_d, retriever_kwargs={"index": sh, "n_probe": 8, "cap_tile": 16})
cfg_s = FOPOConfig(num_items=p, num_samples=33, top_k=16, epsilon=0.5,
                   retriever="exact", fused=True, fused_sampler=True,
                   fused_interpret=True, sample_tile=8)
ld, _ = plan.execute(policy, params, key, x, beta, reward_fn)
ls, _ = fopo_loss(policy, params, key, x, beta, reward_fn, cfg_s)
np.testing.assert_allclose(float(ld), float(ls), rtol=1e-5)
gd = jax.grad(lambda pp: plan.execute(policy, pp, key, x, beta, reward_fn)[0])(params)
gs = jax.grad(lambda pp: fopo_loss(policy, pp, key, x, beta, reward_fn, cfg_s)[0])(params)
np.testing.assert_allclose(np.asarray(gd["w"]), np.asarray(gs["w"]), rtol=1e-5, atol=1e-6)
print("DIST_IVF_OK")
"""
    res = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(__file__)),
        timeout=600,
    )
    assert "DIST_IVF_OK" in res.stdout, res.stderr[-3000:]


@pytest.mark.parametrize("n", [2, 8, 64, 512])
def test_bitonic_merge_matches_lax_top_k_order_with_ties(n):
    """The in-kernel merge network (Mosaic has no lax.top_k lowering)
    sorts exactly as lax.top_k: descending, ties to the lower lane —
    checked on integer-valued scores, so ties are everywhere."""
    from repro.kernels.ivf_topk.kernel import _bitonic_sort_desc

    rng = np.random.default_rng(n)
    s = jnp.asarray(rng.integers(-4, 4, (1, n)).astype(np.float32))
    ids = jnp.asarray(rng.permutation(n).astype(np.int32)[None, :])
    got_s, got_i = jax.jit(_bitonic_sort_desc)(s, ids)
    want_s, pos = jax.lax.top_k(s, n)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    np.testing.assert_array_equal(
        np.asarray(got_i), np.asarray(jnp.take_along_axis(ids, pos, axis=-1))
    )
