"""Subprocess probe for the multi-device fused FOPO step: the ONE
place the dist-vs-single parity check on a forced 4-device host mesh
lives, invoked as `python -m benchmarks.dist_parity_probe` by BOTH
`benchmarks.dist_step` (for the tracked timing/parity row) and
`tests/test_dist.py`'s single-device fallback (for the DIST_OK gate) —
so the two subprocess callers cannot drift apart.

Must run as its own process: the XLA device-count flag only takes
effect before jax initialises its backends. A CPU-only probe: it forces
JAX_PLATFORMS=cpu, so on a machine with a chip it still measures the
host CPU (interpret-mode kernels); `chip_smoke.py --four-chips` is its
chip counterpart.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 "
    + os.environ.get("XLA_FLAGS", "")
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def sampler_probe(dist, b=8, s=50, l=16, p=1201, tile=16, reps=3) -> None:
    """fused_sampler under dist: the per-data-shard in-kernel sampler
    (counter hash keyed by global batch row) must reproduce the
    single-device fused-sampler step — same key -> same draws -> loss
    parity <= 1e-5 and matching user-tower grads — end to end through
    fopo_loss/ExecutionPlan, jitted."""
    import dataclasses

    from repro.core.fopo import FOPOConfig, fopo_loss, make_retriever
    from repro.core.policy import (
        SoftmaxPolicy,
        linear_tower_apply,
        linear_tower_init,
    )
    from repro.core.rewards import make_session_reward

    ks = jax.random.split(jax.random.PRNGKey(42), 4)
    beta = jax.random.normal(ks[0], (p, l))
    x = jax.random.normal(ks[1], (b, l))
    params = linear_tower_init(ks[2], l, l)
    policy = SoftmaxPolicy(tower=linear_tower_apply, item_dim=l)
    positives = jax.random.randint(ks[3], (b, 8), 0, p, dtype=jnp.int32)
    reward_fn = make_session_reward(positives)
    cfg1 = FOPOConfig(
        num_items=p, num_samples=s, top_k=32, epsilon=0.5,
        retriever="streaming", fused=True, fused_sampler=True,
        fused_interpret=True, sample_tile=tile,
    )
    cfgd = dataclasses.replace(cfg1, dist=dist)
    retr = make_retriever(cfg1)
    key = jax.random.PRNGKey(21)

    def single(pp):
        return fopo_loss(policy, pp, key, x, beta, reward_fn, cfg1, retr)[0]

    def sharded(pp):
        return fopo_loss(policy, pp, key, x, beta, reward_fn, cfgd, None)[0]

    j1, j2 = jax.jit(single), jax.jit(sharded)
    l1, l2 = float(j1(params)), float(j2(params))
    rel = abs(l1 - l2) / max(abs(l1), 1e-30)
    assert rel <= 1e-5, (l1, l2)
    g1 = jax.grad(single)(params)
    g2 = jax.grad(sharded)(params)
    np.testing.assert_allclose(
        np.asarray(g2["w"]), np.asarray(g1["w"]), rtol=1e-5, atol=1e-6
    )

    def time_it(f):
        f(params).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            f(params).block_until_ready()
        return (time.perf_counter() - t0) / reps * 1e6

    us1, us2 = time_it(j1), time_it(j2)
    print(
        f"ROW,dist_step_fsampler_cpu4_B{b}_S{s}_L{l}_P{p},{us2:.0f},"
        f"single_us={us1:.0f};devices=4;parity_rel_err={rel:.2e};"
        f"grads_ok=True;sampler=in-kernel"
    )


def main(b=8, s=67, l=16, p=4001, tile=16, reps=3) -> None:
    """Ragged S and P by default, so the routing pad and the catalog
    zero-pad are both on the probed path."""
    from repro.core.gradients import fused_covariance_loss
    from repro.core.policy import (
        SoftmaxPolicy,
        linear_tower_apply,
        linear_tower_init,
    )
    from repro.dist.fopo import dist_fused_covariance_loss, make_debug_dist

    dist = make_debug_dist(2, 2)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    beta = jax.random.normal(ks[0], (p, l))
    x = jax.random.normal(ks[1], (b, l))
    params = linear_tower_init(ks[2], l, l)
    policy = SoftmaxPolicy(tower=linear_tower_apply, item_dim=l)
    actions = jax.random.randint(ks[3], (b, s), 0, p, dtype=jnp.int32)
    log_q = jax.random.normal(ks[4], (b, s)) - 5
    rewards = (jax.random.uniform(ks[5], (b, s)) < 0.3).astype(jnp.float32)
    h = policy.user_embedding(params, x)

    def single(hh):
        return fused_covariance_loss(
            hh, beta, actions, log_q, rewards, interpret=True,
            sample_tile=tile,
        )[0]

    def sharded(hh):
        return dist_fused_covariance_loss(
            hh, beta, actions, log_q, rewards, dist=dist, interpret=True,
            sample_tile=tile,
        )[0]

    l1, l2 = float(single(h)), float(sharded(h))
    rel = abs(l1 - l2) / max(abs(l1), 1e-30)
    g1 = jax.grad(single)(h)
    g2 = jax.grad(sharded)(h)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g1), rtol=1e-5, atol=1e-6)
    assert rel <= 1e-5, (l1, l2)

    j1, j2 = jax.jit(single), jax.jit(sharded)

    def time_it(f):
        f(h).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            f(h).block_until_ready()
        return (time.perf_counter() - t0) / reps * 1e6

    us1, us2 = time_it(j1), time_it(j2)
    jrel = abs(float(j1(h)) - float(j2(h))) / max(abs(float(j1(h))), 1e-30)
    assert jrel <= 1e-5, "jit parity"
    print(
        f"ROW,dist_step_cpu4_B{b}_S{s}_L{l}_P{p},{us2:.0f},"
        f"single_us={us1:.0f};devices=4;parity_rel_err={max(rel, jrel):.2e};"
        f"grads_ok=True"
    )
    # the closed forbidden cell: fused_sampler x dist — its parity gates
    # DIST_OK too, so the tier-1 subprocess fallback covers it
    sampler_probe(dist)
    print("DIST_OK")


if __name__ == "__main__":
    main()
