"""Smoke run of the system's main path on a TPU — the quickest proof
that the program still starts on the chip. Not a benchmark: the times it
prints are one run's set-up, compile and step times.

    python chip_smoke.py               # one chip: train, parity, serve
    python chip_smoke.py --four-chips  # the 2x2-mesh dist step only

Phases on one chip, all through the entry points a user calls and at
the published widths of two configurations:

  (a) train  — fopo-paper CONFIG (P=750,000, L=100, B=32, S=1000,
               K=256, eps=0.8) through `FOPOTrainer` on the fused path:
               Pallas covgrad kernels, the in-kernel sampler and
               `ivf_topk` retrieval over a `build_ivf` index. A few
               steps; the loss must be finite and the tower must move.
  (b) parity — one step with fused=True against fused=False (both with
               the jax.random sampler), same key, same ivf_pallas
               retrieval, matmul precision pinned to "highest": loss and
               tower grad within the bounds of tests/test_fused_step.py,
               the grad's absolute bound scaled to the grad's own size.
  (c) serve  — sasrec CONFIG (1,000,000 items, d=50, seq_len=50, two
               blocks) through `ServingEngine` + `RecsysMIPSRoute`:
               every request answered with valid ids, the plan never
               degraded; the `ivf_topk` kernel route must return the
               ids of the planner's jnp IVF reference (same index, same
               n_probe) on the same requests; prints recall@k against
               the planner's exact fallback plan.

``--four-chips`` runs only the fopo-paper fused step (retrieval, the
in-kernel sampler, the covgrad kernels) on a 2x2 (data x model) mesh of
four devices against the single-device step on the same inputs, within
the parity bounds of tests/test_dist.py.

Every resolved plan must run its kernels compiled (``interpret`` is
False). One process holds the chip(s); nothing is spawned. Any failure
exits non-zero without the result line; on success the last line is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
TRAIN_STEPS = 4
SERVE_REQUESTS, SERVE_MAX_BATCH, SERVE_K = 16, 8, 10
# tests/test_fused_step.py: fused vs jnp chain
FUSED_LOSS_RTOL, FUSED_GRAD_RTOL, FUSED_GRAD_ATOL = 1e-5, 1e-5, 1e-5
# tests/test_dist.py (the dist parity probe): mesh vs one device
DIST_LOSS_RTOL, DIST_GRAD_RTOL, DIST_GRAD_ATOL = 1e-5, 1e-5, 1e-6
# kernel route vs jnp IVF reference, both at matmul precision "highest"
SERVE_SCORE_RTOL = 1e-5


def dense_reward(actions):
    """The parity phases' reward: 1 on every third item id. Session
    targets are a few dozen of 750,000 items, so most rows of one batch
    carry no reward and their covariance term is exactly zero on both
    sides of a comparison; this reward gives every row one."""
    return (actions % 3 == 0).astype("float32")


def log(msg: str) -> None:
    print(f"[chip smoke, one run, not a benchmark] {msg}", flush=True)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _compiled(plan, what: str) -> None:
    _check(plan.interpret is False, f"{what}: plan resolved interpret=True")


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def _grads_close(got, want, rtol: float, atol: float) -> tuple[float, float, float]:
    """Assert two grad pytrees agree within rtol and an absolute bound of
    ``atol`` or ``rtol`` times the reference grad's largest entry,
    whichever is smaller: at the smoke's small losses the grads can sit
    below a fixed atol, which would then accept any values. Returns
    (max |diff|, max |reference grad|, the absolute bound used)."""
    import jax
    import numpy as np

    want = [np.asarray(b) for b in jax.tree.leaves(want)]
    scale = max(float(np.max(np.abs(b))) for b in want)
    _check(scale > 0.0, "grad parity: the reference grad is all zero")
    bound = min(atol, rtol * scale)
    worst = 0.0
    for a, b in zip(jax.tree.leaves(got), want):
        a = np.asarray(a)
        np.testing.assert_allclose(a, b, rtol=rtol, atol=bound)
        worst = max(worst, float(np.max(np.abs(a - b))))
    return worst, scale, bound


# ---------------------------------------------------------------------------
# (a) train + (b) fused-vs-unfused parity
# ---------------------------------------------------------------------------

def train_phases(cfg) -> None:
    """(a) and (b) for a fopo-paper config (the published CONFIG)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.plan import ExecutionPlan
    from repro.launch.train import fopo_paper_trainer

    t0 = time.perf_counter()
    trainer, train_ds, _ = fopo_paper_trainer(cfg, seed=SEED)
    jax.block_until_ready(trainer.beta)
    setup_s = time.perf_counter() - t0
    fc = trainer.cfg.fopo
    _compiled(trainer.plan, "train")
    _check(
        (fc.num_items, trainer.beta.shape[1], trainer.cfg.batch_size,
         fc.num_samples, fc.top_k, fc.epsilon)
        == (cfg.num_items, cfg.embed_dim, cfg.batch_size,
            cfg.fopo.num_samples, cfg.fopo.top_k, cfg.fopo.epsilon),
        f"train: trainer does not run the config's widths: {fc}",
    )
    _check(fc.fused and fc.fused_sampler and fc.retriever == "ivf_pallas",
           f"train: not the fused path: {fc}")
    log(f"train: fopo-paper P={fc.num_items} L={trainer.beta.shape[1]} "
        f"B={trainer.cfg.batch_size} S={fc.num_samples} K={fc.top_k} "
        f"eps={fc.epsilon} fused=True fused_sampler=True "
        f"retriever=ivf_pallas interpret={trainer.plan.interpret} "
        f"sample_tile={trainer.plan.sample_tile}; set-up (data + index) "
        f"{setup_s:.2f} s")

    before = jax.tree.map(np.asarray, trainer.params)
    hist = trainer.train(TRAIN_STEPS)
    losses = [float(v) for v in hist["loss"]]
    times = [float(v) for v in hist["step_time"]]
    _check(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    moved = max(
        float(np.max(np.abs(np.asarray(a) - b)))
        for a, b in zip(jax.tree.leaves(trainer.params), jax.tree.leaves(before))
    )
    _check(moved > 0.0, "train: tower params did not change")
    log(f"train: losses {losses}; first step (compile + run) "
        f"{times[0]:.2f} s; later steps "
        f"{', '.join(f'{t * 1e3:.1f}' for t in times[1:])} ms; "
        f"max |param change| {moved:.3e}")

    # (b) one step fused vs unfused, same key and retrieval
    x = jnp.asarray(train_ds.contexts[: cfg.batch_size])
    key = jax.random.PRNGKey(SEED + 1)
    out = {}
    with jax.default_matmul_precision("highest"):
        for fused in (True, False):
            plan = ExecutionPlan.resolve(
                dataclasses.replace(fc, fused=fused, fused_sampler=False),
                retriever_kwargs={"index": trainer.index_state},
            )
            _compiled(plan, f"parity fused={fused}")
            step = jax.jit(jax.value_and_grad(
                lambda p, beta, index, plan=plan: plan.execute(
                    trainer.policy, p, key, x, beta, dense_reward,
                    index_state=index,
                )[0]
            ))
            loss, grads = step(trainer.params, trainer.beta, trainer.index_state)
            out[fused] = (float(loss), jax.tree.map(np.asarray, grads))
    (lf, gf), (lu, gu) = out[True], out[False]
    loss_rel = _rel(lf, lu)
    _check(np.isfinite(lf) and lf != 0.0 and loss_rel <= FUSED_LOSS_RTOL,
           f"parity: loss fused {lf} vs unfused {lu} (rel {loss_rel:.2e})")
    worst, scale, bound = _grads_close(gf, gu, FUSED_GRAD_RTOL, FUSED_GRAD_ATOL)
    log(f"parity: fused loss {lf:.9g} vs unfused {lu:.9g} (rel "
        f"{loss_rel:.2e} <= {FUSED_LOSS_RTOL:g}); tower grad max |diff| "
        f"{worst:.2e}, max |grad| {scale:.2e} (rtol {FUSED_GRAD_RTOL:g}, "
        f"atol {bound:.2e}); matmul precision highest")


# ---------------------------------------------------------------------------
# (c) serve
# ---------------------------------------------------------------------------

def serve_phase(scale: str = "full") -> None:
    """(c) for the sasrec config of ``scale`` (full: the published one)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch
    from repro.launch.serve import build_route, make_parser
    from repro.serve import CoalescePolicy, ServingEngine

    args = make_parser().parse_args([
        "--arch", "sasrec", "--scale", scale,
        "--requests", str(SERVE_REQUESTS),
        "--max-batch", str(SERVE_MAX_BATCH), "--k", str(SERVE_K),
    ])
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    cfg, route, payload = build_route(get_arch("sasrec"), args, rng)
    setup_s = time.perf_counter() - t0
    planner = route.planner
    _compiled(planner.plan, "serve")
    _compiled(planner.fallback_plan, "serve fallback")
    engine = ServingEngine(
        route, CoalescePolicy(max_batch=args.max_batch,
                              max_wait_s=args.max_wait_ms / 1e3)
    )
    t0 = time.perf_counter()
    engine.warmup()
    warm_s = time.perf_counter() - t0
    payloads = [payload() for _ in range(args.requests)]
    t0 = time.perf_counter()
    for p in payloads:
        engine.submit(p, arrival=0.0)
    records = engine.drain()
    serve_s = time.perf_counter() - t0
    _check(len(records) == args.requests and records.failure is None
           and not records.abandoned,
           f"serve: answered {len(records)} of {args.requests} "
           f"(failure {records.failure})")
    _check(not route.degraded, "serve: plan degraded")
    by_rid = {r.rid: r.result for r in records}
    exact = []
    batches = [jnp.asarray(np.stack(payloads[lo:lo + args.max_batch]))
               for lo in range(0, args.requests, args.max_batch)]
    for x in batches:
        exact.extend(np.asarray(planner.query_exact(x).indices))
    # the kernel route against the jnp IVF reference on the same index
    # and n_probe: at "highest" both score in f32, so only a fault in the
    # kernel's probe, scoring or merge can change the ids
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for x in batches:
            got, ref = planner.query(x), planner.query_reference(x)
            gi, ri = np.asarray(got.indices), np.asarray(ref.indices)
            gs, rs = np.asarray(got.scores), np.asarray(ref.scores)
            for g, r in zip(gi, ri):
                _check(set(g.tolist()) == set(r.tolist()),
                       f"serve: kernel ids {g} != jnp IVF reference ids {r}")
            worst = max(worst, float(np.max(np.abs(gs - rs)) / np.max(np.abs(rs))))
    _check(worst <= SERVE_SCORE_RTOL,
           f"serve: kernel scores off the jnp IVF reference by {worst:.2e} "
           f"of the largest score")
    hits = 0
    for rid, (ids, scores) in sorted(by_rid.items()):
        _check(ids.shape == (args.k,) and np.all((ids >= 0) & (ids < cfg.item_vocab))
               and len(set(ids.tolist())) == args.k and np.all(np.isfinite(scores)),
               f"serve: request {rid} answered with invalid ids {ids}")
        hits += len(set(ids.tolist()) & set(exact[rid].tolist()))
    recall = hits / (args.k * args.requests)
    lat = sorted(r.latency for r in records)
    log(f"serve: sasrec items={cfg.item_vocab} d={cfg.embed_dim} "
        f"seq_len={cfg.seq_len} blocks={cfg.num_blocks}; "
        f"{len(records)}/{args.requests} answered in {engine.batches} "
        f"batches (max_batch {args.max_batch}), degraded=False, "
        f"interpret={planner.plan.interpret}; kernel ids == jnp IVF "
        f"reference ids on {args.requests}/{args.requests} requests (score "
        f"max |diff| / max |score| {worst:.2e} <= {SERVE_SCORE_RTOL:g}, "
        f"matmul precision highest); recall@{args.k} vs exact fallback plan {recall:.4f} "
        f"(n_probe {planner.n_probe}); set-up "
        f"(params + index) {setup_s:.2f} s, warm-up compile {warm_s:.2f} s, "
        f"serve {serve_s * 1e3:.1f} ms, batch latency p50 "
        f"{lat[len(lat) // 2] * 1e3:.1f} ms")


# ---------------------------------------------------------------------------
# --four-chips: the dist fused step on a 2x2 mesh vs one device
# ---------------------------------------------------------------------------

def dist_steps(cfg, mesh, *, fused_interpret=None):
    """The fopo-paper fused step (in-kernel sampler, covgrad kernels) on
    one device and on ``mesh``: returns ((single_plan, single_fn),
    (dist_plan, dist_fn)), each fn the jitted value_and_grad over
    (params, key, x, beta) with `dense_reward`. One device retrieves by exact
    top-K, the mesh by the sharded exact top-K merge — the same
    candidates on both."""
    import jax

    from repro.core.fopo import fopo_loss
    from repro.core.plan import ExecutionPlan
    from repro.core.policy import SoftmaxPolicy, linear_tower_apply
    from repro.dist.fopo import DistConfig

    single_cfg = dataclasses.replace(
        cfg.fopo, retriever="exact", fused=True, fused_sampler=True,
        fused_interpret=fused_interpret,
    )
    policy = SoftmaxPolicy(tower=linear_tower_apply, item_dim=cfg.embed_dim)
    out = []
    for fcfg in (single_cfg,
                 dataclasses.replace(single_cfg, dist=DistConfig(mesh=mesh))):
        plan = ExecutionPlan.resolve(fcfg)

        def loss(params, key, x, beta, plan=plan):
            return plan.execute(policy, params, key, x, beta, dense_reward)[0]

        out.append((plan, jax.jit(jax.value_and_grad(loss))))
    return tuple(out)


def four_chip_phase(cfg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.policy import linear_tower_init
    from repro.data import clustered_sessions
    from repro.launch.mesh import make_debug_mesh

    devices = jax.devices()
    _check(len(devices) == 4, f"--four-chips needs 4 devices, got {len(devices)}")
    mesh = make_debug_mesh(2, 2)
    t0 = time.perf_counter()
    data = clustered_sessions(
        cfg.num_items, cfg.embed_dim, cfg.batch_size, num_clusters=1024,
        seed=SEED,
    )
    (plan1, single), (plan4, dist) = dist_steps(cfg, mesh)
    _compiled(plan1, "four-chip one device")
    _compiled(plan4, "four-chip mesh")
    params = linear_tower_init(jax.random.PRNGKey(SEED), cfg.embed_dim,
                               cfg.embed_dim)
    key = jax.random.PRNGKey(SEED + 1)
    x = jnp.asarray(data.contexts)
    beta1 = jax.device_put(data.item_embeddings, devices[0])
    rows = NamedSharding(mesh, P("model", None))
    batch = NamedSharding(mesh, P("data", None))
    beta4 = jax.device_put(data.item_embeddings, rows)
    x4 = jax.device_put(x, batch)
    setup_s = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        l1, g1 = jax.block_until_ready(single(params, key, x, beta1))
        t_single = time.perf_counter() - t0
        t0 = time.perf_counter()
        l4, g4 = jax.block_until_ready(dist(params, key, x4, beta4))
        t_dist = time.perf_counter() - t0
    l1, l4 = float(l1), float(l4)
    rel = _rel(l4, l1)
    _check(np.isfinite(l1) and l1 != 0.0 and rel <= DIST_LOSS_RTOL,
           f"four-chip: loss mesh {l4} vs one device {l1} (rel {rel:.2e})")
    worst, scale, bound = _grads_close(g4, g1, DIST_GRAD_RTOL, DIST_GRAD_ATOL)
    log(f"four-chip: fopo-paper fused step (in-kernel sampler, covgrad "
        f"kernels) P={cfg.num_items} L={cfg.embed_dim} B={cfg.batch_size} "
        f"S={cfg.fopo.num_samples} K={cfg.fopo.top_k} on a 2x2 "
        f"(data x model) mesh vs one device: loss {l4:.9g} vs {l1:.9g} "
        f"(rel {rel:.2e} <= {DIST_LOSS_RTOL:g}); grad max |diff| "
        f"{worst:.2e}, max |grad| {scale:.2e} (rtol {DIST_GRAD_RTOL:g}, "
        f"atol {bound:.2e}); "
        f"interpret={plan4.interpret}; matmul precision highest; set-up "
        f"{setup_s:.2f} s, first call (compile + run) one device "
        f"{t_single:.2f} s, mesh {t_dist:.2f} s")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh dist step against one device")
    args = ap.parse_args()
    try:
        import jax

        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the program is not importable: {e}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform}); "
              "nothing run", file=sys.stderr)
        return 1
    log(f"devices: {len(devices)} x {devices[0].device_kind}; compile cache "
        f"{enable_compile_cache()}")
    from repro.configs import get_arch

    paper = get_arch("fopo-paper").CONFIG
    if args.four_chips:
        four_chip_phase(paper)
    else:
        train_phases(paper)
        serve_phase("full")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
