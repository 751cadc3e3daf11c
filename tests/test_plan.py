"""ExecutionPlan: construction-time knob validation (every invalid
combination fails at resolve, before tracing), one-shot resolution of
interpret/tile/retriever, and the shared step skeleton — including the
previously forbidden fused_sampler x dist cell, exercised here on a
1x1 mesh so tier-1 covers it on a single device."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ExecutionPlan, FOPOConfig, fopo_loss
from repro.core.plan import resolve_interpret
from repro.core.policy import SoftmaxPolicy, linear_tower_apply, linear_tower_init
from repro.core.rewards import make_session_reward


def _fopo_problem(seed=0, b=4, l=12, p=160):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    beta = jax.random.normal(ks[0], (p, l))
    x = jax.random.normal(ks[1], (b, l))
    params = linear_tower_init(ks[2], l, l)
    policy = SoftmaxPolicy(tower=linear_tower_apply, item_dim=l)
    positives = jax.random.randint(ks[3], (b, 6), 0, p, dtype=jnp.int32)
    return policy, params, x, beta, make_session_reward(positives)


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------

def test_resolve_normalizes_tile_and_interpret():
    cfg = FOPOConfig(num_items=100, num_samples=10, sample_tile=64, fused=True)
    plan = ExecutionPlan.resolve(cfg, backend="cpu")
    assert plan.sample_tile == 10  # clamped to num_samples
    assert plan.cfg.sample_tile == 10  # written back
    assert plan.interpret is True  # cpu -> interpret fallback
    assert plan.cfg.fused_interpret is True
    assert plan.fused is True and plan.dist is None
    assert callable(plan.retriever)


def test_resolve_tpu_backend_selects_compiled_kernels():
    cfg = FOPOConfig(num_items=100, fused=True)
    assert ExecutionPlan.resolve(cfg, backend="tpu").interpret is False
    # an explicit setting always wins
    cfg = FOPOConfig(num_items=100, fused=True, fused_interpret=True)
    assert ExecutionPlan.resolve(cfg, backend="tpu").interpret is True
    assert resolve_interpret(None, "tpu") is False
    assert resolve_interpret(False, "cpu") is False


@pytest.mark.parametrize("route", ["ivf_pallas", "refresh", "fallback"])
def test_tpu_plan_hands_compiled_mode_to_every_kernel(route, monkeypatch):
    """On backend="tpu" every Pallas kernel the step traces — covgrad
    forward and backward, the in-kernel sampler and the ivf_topk
    retriever — is handed interpret=False, on the plain ivf_pallas plan,
    the refresh plan and its degraded exact-fallback plan alike. The
    step is only traced (eval_shape), so nothing is lowered on CPU."""
    from jax.experimental import pallas as pl

    from repro.mips.ivf import build_ivf
    from repro.mips.refresh import RefreshConfig

    seen = []
    real_pallas_call = pl.pallas_call

    def spy(kernel, *args, **kwargs):
        name = getattr(kernel, "__name__", None) or kernel.func.__name__
        seen.append((name, kwargs.get("interpret")))
        return real_pallas_call(kernel, *args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", spy)
    jax.clear_caches()  # no cached trace may skip the spy

    policy, params, x, beta, reward_fn = _fopo_problem(seed=4, b=4, l=12, p=160)
    index = build_ivf(jax.random.PRNGKey(0), beta, num_clusters=4, cap_tile=8)
    cfg = FOPOConfig(
        num_items=160, num_samples=20, top_k=8, epsilon=0.5,
        retriever="ivf_pallas", fused=True, fused_sampler=True, sample_tile=8,
        index_refresh=None if route == "ivf_pallas" else RefreshConfig(
            every=0, compact_every=0, delta_cap=8
        ),
    )
    plan = ExecutionPlan.resolve(
        cfg, backend="tpu", retriever_kwargs={"index": index, "n_probe": 2}
    )
    if route == "fallback":
        plan = plan.degrade_to_fallback()
    assert plan.interpret is False and plan.cfg.fused_interpret is False
    key = jax.random.PRNGKey(0)
    jax.eval_shape(
        jax.grad(
            lambda pp: plan.execute(policy, pp, key, x, beta, reward_fn)[0]
        ),
        params,
    )
    kernels = {name for name, _ in seen}
    expected = {
        "_fused_sampler_kernel", "_fused_fwd_tiled_kernel",
        "_fused_bwd_tiled_kernel",
    }
    if route != "fallback":
        expected.add("_ivf_topk_kernel")
    else:
        assert "_ivf_topk_kernel" not in kernels  # exact top-K retrieval
    assert expected <= kernels, kernels
    assert all(mode is False for _, mode in seen), seen


def test_resolve_leaves_unfused_config_untouched():
    """The unfused jnp path never resolved fused_interpret before; the
    plan keeps that contract (cfg round-trips unchanged)."""
    cfg = FOPOConfig(num_items=100, retriever="exact")
    plan = ExecutionPlan.resolve(cfg, backend="cpu")
    assert plan.cfg.fused_interpret is None
    assert plan.fused is False and plan.fused_sampler is False


def test_resolve_fills_num_items():
    plan = ExecutionPlan.resolve(FOPOConfig(num_items=0), num_items=321)
    assert plan.cfg.num_items == 321


def test_injected_retriever_passes_through():
    marker = lambda h, beta: None  # noqa: E731
    plan = ExecutionPlan.resolve(
        FOPOConfig(num_items=10, retriever="ivf"), retriever=marker
    )  # no index kwarg needed: injection skips construction
    assert plan.retriever is marker


# ---------------------------------------------------------------------------
# validation — every invalid knob combination fails at construction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "cfg_kwargs,match",
    [
        (dict(num_items=0), "num_items"),
        (dict(num_items=-3), "num_items"),
        (dict(num_items=10, num_samples=0), "num_samples"),
        (dict(num_items=10, top_k=0), "top_k"),
        (dict(num_items=10, epsilon=-0.1), "epsilon"),
        (dict(num_items=10, epsilon=1.5), "epsilon"),
        (dict(num_items=10, epsilon=2), "epsilon"),  # int bypass regression
        (dict(num_items=10, retriever="nope"), "unknown retriever"),
        (dict(num_items=10, retriever="ivf"), "index"),
        (dict(num_items=10, retriever="ivf_pallas"), "index"),
        (dict(num_items=10, retriever="sharded"), "mesh"),
    ],
)
def test_invalid_knobs_fail_at_resolve(cfg_kwargs, match):
    with pytest.raises((ValueError, TypeError), match=match):
        ExecutionPlan.resolve(FOPOConfig(**cfg_kwargs))


def test_non_distconfig_dist_rejected():
    """dist= must be a DistConfig — garbage fails at plan construction
    (this replaces the old fused_sampler x dist ValueError guards; that
    combination itself is now SUPPORTED)."""

    class _FakeDist:
        pass

    cfg = FOPOConfig(num_items=10, dist=_FakeDist())
    with pytest.raises(ValueError, match="DistConfig"):
        ExecutionPlan.resolve(cfg)


def test_trainer_surfaces_plan_validation():
    """FOPOTrainer construction runs plan validation (the old duplicated
    trainer/dist guards are gone)."""
    from repro.data import SyntheticConfig, generate_sessions
    from repro.train import FOPOTrainer, TrainerConfig

    ds = generate_sessions(
        SyntheticConfig(num_items=60, num_users=16, embed_dim=8,
                        session_len=4, seed=0)
    )
    bad = FOPOConfig(num_items=0, retriever="nope")
    with pytest.raises(ValueError, match="unknown retriever"):
        FOPOTrainer(TrainerConfig(estimator="fopo", fopo=bad), ds)


def test_fused_sampler_with_dist_is_allowed():
    """The forbidden cell is closed: fused_sampler x dist resolves."""
    from repro.dist.fopo import make_debug_dist

    cfg = FOPOConfig(
        num_items=64, fused_sampler=True, dist=make_debug_dist(1, 1)
    )
    plan = ExecutionPlan.resolve(cfg, backend="cpu")
    assert plan.fused_sampler and plan.dist is not None
    assert plan.retriever is None  # sharded top-K owns retrieval


# ---------------------------------------------------------------------------
# the shared skeleton — fused_sampler x dist on a 1x1 mesh (tier-1)
# ---------------------------------------------------------------------------

def test_dist_fused_sampler_1x1_mesh_matches_single_device():
    """fopo_loss(dist=1x1 mesh, fused_sampler=True) reproduces the
    single-device fused-sampler path: the per-shard in-kernel sampler
    at row offset 0 IS the single-device stream, so loss and grads
    match to reduction reassociation."""
    from repro.dist.fopo import make_debug_dist

    policy, params, x, beta, reward_fn = _fopo_problem(seed=3, b=4, p=160)
    single = FOPOConfig(
        num_items=160, num_samples=33, top_k=16, epsilon=0.5,
        retriever="exact", fused=True, fused_sampler=True,
        fused_interpret=True, sample_tile=8,
    )
    dist = dataclasses.replace(
        single, retriever="streaming", dist=make_debug_dist(1, 1)
    )
    key = jax.random.PRNGKey(5)

    l1, _ = fopo_loss(policy, params, key, x, beta, reward_fn, single)
    l2, _ = fopo_loss(policy, params, key, x, beta, reward_fn, dist)
    np.testing.assert_allclose(float(l2), float(l1), rtol=1e-6)

    g1 = jax.grad(
        lambda pp: fopo_loss(policy, pp, key, x, beta, reward_fn, single)[0]
    )(params)
    g2 = jax.grad(
        lambda pp: fopo_loss(policy, pp, key, x, beta, reward_fn, dist)[0]
    )(params)
    np.testing.assert_allclose(
        np.asarray(g2["w"]), np.asarray(g1["w"]), rtol=1e-5, atol=1e-6
    )


def test_plan_execute_equals_fopo_loss_per_call_resolution():
    """A prebuilt plan (the trainer's hot path) and per-call resolution
    are the same step: identical loss at identical keys."""
    policy, params, x, beta, reward_fn = _fopo_problem(seed=9)
    cfg = FOPOConfig(
        num_items=160, num_samples=24, top_k=12, epsilon=0.7,
        retriever="exact", fused=True, fused_interpret=True, sample_tile=8,
    )
    plan = ExecutionPlan.resolve(cfg)
    key = jax.random.PRNGKey(1)
    l1, _ = fopo_loss(policy, params, key, x, beta, reward_fn, cfg)
    l2, _ = plan.execute(policy, params, key, x, beta, reward_fn)
    assert float(l1) == float(l2)
