"""Multi-device fused FOPO step (repro.dist): sharded-vs-single-device
parity on a 4-way host-CPU mesh (data x model = 2 x 2).

The in-process tests need >= 4 devices (the CI dist job forces them via
XLA_FLAGS=--xla_force_host_platform_device_count=4); under plain tier-1
(single device) a subprocess fallback runs the core parity check so the
dist path never goes untested.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

MULTI = jax.device_count() >= 4

multi_device = pytest.mark.skipif(
    not MULTI,
    reason="needs >= 4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)",
)


def _problem(seed, b=4, s=37, l=12, p=203):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    from repro.core.policy import (
        SoftmaxPolicy,
        linear_tower_apply,
        linear_tower_init,
    )

    beta = jax.random.normal(ks[0], (p, l))
    x = jax.random.normal(ks[1], (b, l))
    params = linear_tower_init(ks[2], l, l)
    policy = SoftmaxPolicy(tower=linear_tower_apply, item_dim=l)
    actions = jax.random.randint(ks[3], (b, s), 0, p, dtype=jnp.int32)
    log_q = jax.random.normal(ks[4], (b, s)) - 5
    rewards = (jax.random.uniform(ks[5], (b, s)) < 0.3).astype(jnp.float32)
    return policy, params, x, beta, actions, log_q, rewards


@pytest.fixture(scope="module")
def dist22():
    from repro.dist.fopo import make_debug_dist

    return make_debug_dist(2, 2)


# ---------------------------------------------------------------------------
# surrogate-level parity: dist_fused_covariance_loss vs fused_covariance_loss
# ---------------------------------------------------------------------------

@multi_device
@pytest.mark.parametrize("routing", ["gather", "replicate"])
@pytest.mark.parametrize(
    "seed,b,s,l,p",
    [
        (0, 4, 37, 12, 203),  # ragged P (203 % 2 != 0) AND ragged S
        (1, 8, 24, 8, 64),  # everything divides
        (2, 4, 5, 16, 301),  # S < any reasonable tile; ragged P
    ],
)
def test_dist_loss_and_grads_match_single_device(dist22, routing, seed, b, s, l, p):
    """Per-slot sampled scores reconstruct BITWISE (each slot receives
    its owner's kernel value plus exact zeros through the psum); the
    scalar loss/aux then match to float-sum reassociation of the final
    batch reduction over the data-sharded rows (<= 1e-6 rel, well
    inside the 1e-5 acceptance bar), and grad_h to <= 1e-5."""
    import dataclasses

    from repro.core.gradients import fused_covariance_loss
    from repro.dist.fopo import dist_fused_covariance_loss, dist_score_partials
    from repro.kernels.snis_covgrad.ops import snis_scores_fused

    d = dataclasses.replace(dist22, routing=routing)
    policy, params, x, beta, actions, log_q, rewards = _problem(seed, b, s, l, p)
    h = policy.user_embedding(params, x)

    # the exactness core: summing the per-shard partials (owner value +
    # hard zeros) reproduces the single-device kernel scores bit for bit
    parts = np.asarray(dist_score_partials(
        h, beta, actions, log_q, rewards, dist=d, interpret=True,
        sample_tile=8,
    ))
    ref_scores = np.asarray(snis_scores_fused(
        h, beta, actions, log_q, rewards, interpret=True, sample_tile=8
    ))
    np.testing.assert_array_equal(parts.sum(axis=0)[:, :s], ref_scores)

    loss1, aux1 = fused_covariance_loss(
        h, beta, actions, log_q, rewards, interpret=True, sample_tile=8
    )
    loss2, aux2 = dist_fused_covariance_loss(
        h, beta, actions, log_q, rewards, dist=d, interpret=True, sample_tile=8
    )
    np.testing.assert_allclose(float(loss2), float(loss1), rtol=1e-6)
    for k in aux1:
        np.testing.assert_allclose(float(aux2[k]), float(aux1[k]), rtol=1e-6)

    g1 = jax.grad(
        lambda hh: fused_covariance_loss(
            hh, beta, actions, log_q, rewards, interpret=True, sample_tile=8
        )[0]
    )(h)
    g2 = jax.grad(
        lambda hh: dist_fused_covariance_loss(
            hh, beta, actions, log_q, rewards,
            dist=d, interpret=True, sample_tile=8,
        )[0]
    )(h)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g1), rtol=1e-5, atol=1e-6)


@multi_device
def test_dist_fopo_loss_end_to_end_parity(dist22):
    """fopo_loss(dist=...) == fopo_loss(single, fused): identical keys
    drive identical retrieval -> identical draws -> identical loss, and
    the parameter gradients through the user tower agree <= 1e-5."""
    import dataclasses

    from repro.core.fopo import FOPOConfig, fopo_loss, make_retriever
    from repro.core.rewards import make_session_reward

    policy, params, x, beta, _, _, _ = _problem(3, b=6, l=16, p=501)
    positives = jax.random.randint(
        jax.random.PRNGKey(9), (6, 8), 0, 501, dtype=jnp.int32
    )
    reward_fn = make_session_reward(positives)
    cfg1 = FOPOConfig(
        num_items=501, num_samples=50, top_k=32, epsilon=0.5,
        retriever="streaming", fused=True, fused_interpret=True, sample_tile=8,
    )
    cfgd = dataclasses.replace(cfg1, dist=dist22)
    retr = make_retriever(cfg1)
    key = jax.random.PRNGKey(7)

    l1, _ = fopo_loss(policy, params, key, x, beta, reward_fn, cfg1, retr)
    l2, _ = fopo_loss(policy, params, key, x, beta, reward_fn, cfgd, None)
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-6)

    g1 = jax.grad(
        lambda pp: fopo_loss(policy, pp, key, x, beta, reward_fn, cfg1, retr)[0]
    )(params)
    g2 = jax.grad(
        lambda pp: fopo_loss(policy, pp, key, x, beta, reward_fn, cfgd, None)[0]
    )(params)
    np.testing.assert_allclose(
        np.asarray(g2["w"]), np.asarray(g1["w"]), rtol=1e-5, atol=1e-6
    )


@multi_device
def test_dist_uniform_eps_branch(dist22):
    """eps >= 1 skips retrieval entirely (uniform proposal) and still
    matches the single-device path draw for draw."""
    import dataclasses

    from repro.core.fopo import FOPOConfig, fopo_loss, make_retriever
    from repro.core.rewards import make_session_reward

    policy, params, x, beta, _, _, _ = _problem(4, b=4, l=12, p=203)
    positives = jax.random.randint(
        jax.random.PRNGKey(2), (4, 8), 0, 203, dtype=jnp.int32
    )
    reward_fn = make_session_reward(positives)
    cfg1 = FOPOConfig(
        num_items=203, num_samples=40, top_k=16, epsilon=1.0,
        retriever="exact", fused=True, fused_interpret=True,
    )
    cfgd = dataclasses.replace(cfg1, dist=dist22)
    key = jax.random.PRNGKey(11)
    l1, _ = fopo_loss(policy, params, key, x, beta, reward_fn, cfg1, make_retriever(cfg1))
    l2, _ = fopo_loss(policy, params, key, x, beta, reward_fn, cfgd, None)
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-6)


# ---------------------------------------------------------------------------
# structural properties
# ---------------------------------------------------------------------------

@multi_device
def test_all_foreign_ids_shard_contributes_exact_zero(dist22):
    """A device that owns NONE of the sampled ids produces an exactly
    zero score partial — the psum is owner + hard zeros, never noise."""
    from repro.dist.fopo import dist_score_partials

    policy, params, x, beta, actions, log_q, rewards = _problem(5, p=200)
    # every id in shard 0's row range [0, 100) -> shard 1 sees only
    # foreign ids
    actions = actions % 100
    h = policy.user_embedding(params, x)
    parts = dist_score_partials(
        h, beta, actions, log_q, rewards, dist=dist22, interpret=True,
        sample_tile=8,
    )
    parts = np.asarray(parts)
    assert parts.shape[0] == 2
    assert np.all(parts[1] == 0.0)  # exact zero, not just small
    assert np.any(parts[0] != 0.0)


@multi_device
def test_snis_normalizer_psum_exactly_once(dist22):
    """The forward graph contains exactly ONE psum: the score-partial
    reduction the normaliser is derived from. (routing="replicate"
    keeps the graph free of other collectives.)"""
    import dataclasses

    from repro.dist.fopo import dist_fused_covariance_loss

    d = dataclasses.replace(dist22, routing="replicate")
    policy, params, x, beta, actions, log_q, rewards = _problem(6, p=64)
    h = policy.user_embedding(params, x)
    jaxpr = jax.make_jaxpr(
        lambda hh: dist_fused_covariance_loss(
            hh, beta, actions, log_q, rewards, dist=d, interpret=True,
            sample_tile=8,
        )[0]
    )(h)
    assert str(jaxpr).count("psum") == 1


@multi_device
def test_batch_must_divide_data_axis(dist22):
    from repro.dist.fopo import dist_fused_covariance_loss

    policy, params, x, beta, actions, log_q, rewards = _problem(0, b=4)
    h = policy.user_embedding(params, x)
    with pytest.raises(ValueError, match="data-axis"):
        dist_fused_covariance_loss(
            h[:3], beta, actions[:3], log_q[:3], rewards[:3],
            dist=dist22, interpret=True,
        )


@multi_device
def test_dist_sharded_topk_masks_ragged_padding(dist22):
    """Retrieval over a ragged catalog never returns a pad-row id, even
    when most real scores are negative (pad rows score exactly 0)."""
    from repro.dist.fopo import dist_sharded_topk
    from repro.mips.exact import topk_exact

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    p, l, b, k = 203, 8, 4, 64
    beta = jax.random.normal(k1, (p, l))
    h = jax.random.normal(k2, (b, l))
    out = dist_sharded_topk(h, beta, k, dist22)
    ref = topk_exact(h, beta, k)
    assert np.asarray(out.indices).max() < p
    assert (
        np.sort(np.asarray(out.indices), -1)
        == np.sort(np.asarray(ref.indices), -1)
    ).all()
    np.testing.assert_allclose(
        np.sort(np.asarray(out.scores), -1),
        np.sort(np.asarray(ref.scores), -1),
        rtol=1e-5,
    )


@multi_device
def test_dist_sharded_topk_ragged_all_negative_scores(dist22):
    """Adversarial ragged case: every real score is negative, so the
    zero-scoring pad rows would win every local top-K slot they can
    reach. The widened local K + pre-merge demotion must still return
    exactly the dense oracle's top-K."""
    from repro.dist.fopo import dist_sharded_topk
    from repro.mips.exact import topk_exact

    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    p, l, b, k = 203, 8, 4, 64
    # beta rows anti-aligned with every query: scores strictly negative
    beta = -jnp.abs(jax.random.normal(k1, (p, l))) - 0.1
    h = jnp.abs(jax.random.normal(k2, (b, l))) + 0.1
    out = dist_sharded_topk(h, beta, k, dist22)
    ref = topk_exact(h, beta, k)
    assert np.asarray(out.scores).max() < 0.0  # no pad row leaked
    assert np.asarray(out.indices).min() >= 0
    assert (
        np.sort(np.asarray(out.indices), -1)
        == np.sort(np.asarray(ref.indices), -1)
    ).all()


@multi_device
def test_covariance_surrogate_dist_kwarg(dist22):
    """The covariance_surrogate(dist=...) entry point is the same
    multi-device step (parity with fused=True)."""
    from repro.core.gradients import covariance_surrogate

    policy, params, x, beta, actions, log_q, rewards = _problem(7, p=64)
    l1, _ = covariance_surrogate(
        policy, params, x, beta, actions, log_q, rewards,
        fused=True, fused_interpret=True, sample_tile=8,
    )
    l2, _ = covariance_surrogate(
        policy, params, x, beta, actions, log_q, rewards,
        fused_interpret=True, sample_tile=8, dist=dist22,
    )
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-6)


@multi_device
def test_dist_trainer_trajectory_matches_single_device(dist22):
    """The jitted dist trainer walks the same parameter trajectory as
    the single-device fused trainer (same seeds/data). Guards the
    sampling contract: the dist path draws with plain jax.random under
    the trainer's jit, so the draws must not change when the partitioner
    reshards the sampling ops (jax's partitionable threefry) — a
    changed stream shows up as the same distribution on a different
    trajectory, with no error."""
    import dataclasses

    from repro.core.fopo import FOPOConfig
    from repro.data import SyntheticConfig, generate_sessions
    from repro.train import FOPOTrainer, TrainerConfig

    ds = generate_sessions(
        SyntheticConfig(
            num_items=400, num_users=128, embed_dim=16, session_len=8, seed=1
        )
    )
    base = FOPOConfig(
        num_items=400, num_samples=48, top_k=24, epsilon=0.8,
        retriever="exact", fused=True,
    )
    tc = dict(batch_size=8, learning_rate=3e-3, num_steps=4, checkpoint_every=0)
    tr1 = FOPOTrainer(
        TrainerConfig(estimator="fopo", fopo=base, **tc), ds
    )
    tr2 = FOPOTrainer(
        TrainerConfig(
            estimator="fopo",
            fopo=dataclasses.replace(base, retriever="streaming", fused=False, dist=dist22),
            **tc,
        ),
        ds,
    )
    h1 = tr1.train(4)
    h2 = tr2.train(4)
    np.testing.assert_allclose(h2["loss"], h1["loss"], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(tr2.params["w"]), np.asarray(tr1.params["w"]),
        rtol=1e-4, atol=1e-6,
    )


@multi_device
def test_dist_trainer_smoke(dist22):
    """FOPOTrainer(FOPOConfig(dist=...)) trains end to end under jit
    with data-parallel batches and the row-sharded catalog."""
    import dataclasses

    from repro.core.fopo import FOPOConfig
    from repro.data import SyntheticConfig, generate_sessions
    from repro.train import FOPOTrainer, TrainerConfig

    ds = generate_sessions(
        SyntheticConfig(
            num_items=500, num_users=64, embed_dim=16, session_len=8, seed=0
        )
    )
    fopo = FOPOConfig(
        num_items=0, num_samples=40, top_k=32, epsilon=0.5,
        fused_interpret=True, sample_tile=8, dist=dist22,
    )
    tc = TrainerConfig(
        estimator="fopo", fopo=fopo, batch_size=8, num_steps=3,
        checkpoint_every=0,
    )
    tr = FOPOTrainer(tc, ds)
    hist = tr.train(3)
    assert len(hist["loss"]) == 3
    assert all(np.isfinite(v) for v in hist["loss"])


def test_garbage_dist_config_rejected():
    """Config error fires everywhere (no devices needed): ExecutionPlan
    validation — which replaced the duplicated trainer/dist
    fused_sampler x dist ValueError guards — rejects a non-DistConfig
    dist before any mesh use. (fused_sampler + dist itself is now a
    supported combination; see the dist fused-sampler tests above and
    tests/test_plan.py.)"""
    from repro.core.fopo import FOPOConfig

    class _FakeDist:
        pass

    from repro.data import SyntheticConfig, generate_sessions
    from repro.train import FOPOTrainer, TrainerConfig

    ds = generate_sessions(
        SyntheticConfig(
            num_items=100, num_users=16, embed_dim=8, session_len=4, seed=0
        )
    )
    fopo = FOPOConfig(num_items=0, fused_sampler=True, dist=_FakeDist())
    with pytest.raises(ValueError, match="DistConfig"):
        FOPOTrainer(TrainerConfig(estimator="fopo", fopo=fopo), ds)


# ---------------------------------------------------------------------------
# the closed forbidden cell: fused_sampler x dist
# ---------------------------------------------------------------------------

@multi_device
def test_dist_fused_sampler_hash_twin(dist22):
    """Per-shard in-kernel draws ARE the single-device sampler stream:
    the assembled (B, Sp) dist output equals the pure-jnp hash twin of
    the single-device kernel (row_offset 0) bit for bit — each data
    shard reproduced exactly its global rows, so streams are disjoint
    across shards and invariant to the mesh shape."""
    from repro.dist.fopo import dist_fused_mixture_sample
    from repro.kernels.fused_sampler import (
        fused_mixture_sample,
        fused_sampler_ref,
        key_to_seed,
    )
    from repro.mips.exact import TopK

    b, p, k, s, ts, eps = 4, 500, 16, 37, 8, 0.45
    ks = jax.random.split(jax.random.PRNGKey(31), 2)
    scores = jax.random.normal(ks[0], (b, k)) * 2
    ids = jnp.stack(
        [jax.random.permutation(jax.random.PRNGKey(40 + i), p)[:k]
         for i in range(b)]
    ).astype(jnp.int32)
    key = jax.random.PRNGKey(13)

    out = dist_fused_mixture_sample(
        key, TopK(scores=scores, indices=ids),
        num_samples=s, epsilon=eps, num_items=p, sample_tile=ts,
        dist=dist22, interpret=True,
    )
    ra, rq, rs = fused_sampler_ref(
        key_to_seed(key), eps, ids, scores,
        num_samples=s, num_items=p, sample_tile=ts,
    )
    np.testing.assert_array_equal(np.asarray(out.actions), np.asarray(ra))
    np.testing.assert_allclose(
        np.asarray(out.log_q), np.asarray(rq), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(out.topk_slot), np.asarray(rs))
    # ... and hence equals the single-device kernel's stream exactly
    sa, sq, _ = fused_mixture_sample(
        key, ids, scores, num_samples=s, epsilon=eps, num_items=p,
        sample_tile=ts, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(out.actions), np.asarray(sa))
    np.testing.assert_allclose(
        np.asarray(out.log_q), np.asarray(sq), rtol=1e-6, atol=1e-6
    )


@multi_device
def test_dist_fused_sampler_loss_and_grads_match_single_device(dist22):
    """fopo_loss(dist=..., fused_sampler=True) == the single-device
    fused-sampler path at equal keys: identical in-kernel draws (hash
    twin above) -> loss to ~1e-6 (reduction reassociation only) and
    user-tower grads to <= 1e-5 — the established dist parity bar, now
    on the fastest sampler instead of the jax.random fallback."""
    import dataclasses

    from repro.core.fopo import FOPOConfig, fopo_loss, make_retriever
    from repro.core.rewards import make_session_reward

    policy, params, x, beta, _, _, _ = _problem(8, b=6, l=16, p=501)
    positives = jax.random.randint(
        jax.random.PRNGKey(9), (6, 8), 0, 501, dtype=jnp.int32
    )
    reward_fn = make_session_reward(positives)
    cfg1 = FOPOConfig(
        num_items=501, num_samples=50, top_k=32, epsilon=0.5,
        retriever="streaming", fused=True, fused_sampler=True,
        fused_interpret=True, sample_tile=8,
    )
    cfgd = dataclasses.replace(cfg1, dist=dist22)
    retr = make_retriever(cfg1)
    key = jax.random.PRNGKey(7)

    l1, aux1 = fopo_loss(policy, params, key, x, beta, reward_fn, cfg1, retr)
    l2, aux2 = fopo_loss(policy, params, key, x, beta, reward_fn, cfgd, None)
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-6)
    for k in aux1:
        np.testing.assert_allclose(float(aux2[k]), float(aux1[k]), rtol=1e-6)

    g1 = jax.grad(
        lambda pp: fopo_loss(policy, pp, key, x, beta, reward_fn, cfg1, retr)[0]
    )(params)
    g2 = jax.grad(
        lambda pp: fopo_loss(policy, pp, key, x, beta, reward_fn, cfgd, None)[0]
    )(params)
    np.testing.assert_allclose(
        np.asarray(g2["w"]), np.asarray(g1["w"]), rtol=1e-5, atol=1e-6
    )


@multi_device
def test_dist_trainer_fused_sampler_trajectory_matches_single_device(dist22):
    """FOPOConfig(dist=..., fused_sampler=True) trains end to end under
    jit on the 2x2 mesh and walks the same parameter trajectory as the
    single-device fused-sampler trainer (same seeds/data: the row-offset
    counter fold makes the in-kernel draws identical)."""
    import dataclasses

    from repro.core.fopo import FOPOConfig
    from repro.data import SyntheticConfig, generate_sessions
    from repro.train import FOPOTrainer, TrainerConfig

    ds = generate_sessions(
        SyntheticConfig(
            num_items=400, num_users=128, embed_dim=16, session_len=8, seed=1
        )
    )
    base = FOPOConfig(
        num_items=400, num_samples=48, top_k=24, epsilon=0.8,
        retriever="exact", fused=True, fused_sampler=True, sample_tile=16,
    )
    tc = dict(batch_size=8, learning_rate=3e-3, num_steps=4, checkpoint_every=0)
    tr1 = FOPOTrainer(TrainerConfig(estimator="fopo", fopo=base, **tc), ds)
    tr2 = FOPOTrainer(
        TrainerConfig(
            estimator="fopo",
            fopo=dataclasses.replace(
                base, retriever="streaming", fused=False, dist=dist22
            ),
            **tc,
        ),
        ds,
    )
    h1 = tr1.train(4)
    h2 = tr2.train(4)
    np.testing.assert_allclose(h2["loss"], h1["loss"], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(tr2.params["w"]), np.asarray(tr1.params["w"]),
        rtol=1e-4, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# single-device fallback: run the core parity check in a subprocess with
# forced host devices, so tier-1 covers the dist path too
# ---------------------------------------------------------------------------

@pytest.mark.skipif(MULTI, reason="covered in-process on multi-device runs")
def test_dist_parity_subprocess():
    """Runs the shared probe (`benchmarks.dist_parity_probe` — the same
    module the dist_step benchmark invokes) on a forced 4-device mesh:
    eager + jitted loss parity <= 1e-5 rel and grad parity <= 1e-5 on
    ragged S and P, gated by its DIST_OK print."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.dist_parity_probe"],
        capture_output=True,
        text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(root, "src")},
        cwd=root,
        timeout=600,
    )
    assert "DIST_OK" in res.stdout, res.stderr[-3000:]
