"""Serving launcher: the continuous-batching engine (repro.serve) for a
pool arch — recsys retrieval through the `ivf_topk` plan retriever, or
LM prefill + greedy decode with every next-token choice through the
same query-only plan path.

    PYTHONPATH=src python -m repro.launch.serve --arch sasrec --requests 64
    PYTHONPATH=src python -m repro.launch.serve --arch din --requests 16
    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --requests 8
    PYTHONPATH=src python -m repro.launch.serve --arch sasrec --scale full

``--scale full`` serves the arch's published ``CONFIG`` (sasrec: a
1,000,000-item catalog, d=50) instead of ``SMOKE_CONFIG``; the weights
are random, made from a fixed seed.

Requests are enqueued on a virtual arrival clock (``--qps`` spaces
them; 0 = all at once, the closed-loop shape) and coalesced into padded
micro-batches under ``--max-batch`` / ``--max-wait-ms``. Serving rides
the telemetry spine (repro.obs): per-request queue-wait/latency
timings, per-batch service spans and occupancy gauges. `--obs-dir DIR`
leaves metrics.jsonl + trace.json behind for
`python -m repro.obs.report DIR` (which renders a Serving section).
`--ladder` arms the retrieval degradation ladder on the live index for
the MIPS archs (sasrec/dien).

``--replicas N`` (N > 1) serves the same stream through the cluster
dispatcher instead: N route replicas (each with its own index copy)
behind least-loaded routing, health checks and bounded retry
(repro.serve.cluster). ``--chaos`` scripts a replica death mid-traffic
(kill replica 1 at its 3rd dispatch) — the run must still answer every
request by re-queuing onto survivors; the summary prints the retry/
death counters and the per-replica load split.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import arch_config


def build_route(mod, args, rng):
    """Resolve the arch's serving route + a payload generator."""
    cfg = arch_config(mod, args.scale)
    if mod.FAMILY == "lm":
        from repro.models import lm
        from repro.serve import LMGenerateRoute

        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        route = LMGenerateRoute(
            cfg, params, prompt_len=args.prompt_len, gen_len=args.gen_len,
            max_batch=args.max_batch,
        )
        payload = lambda: rng.integers(
            0, cfg.vocab_size, (args.prompt_len,)
        ).astype(np.int32)
        return cfg, route, payload
    if mod.FAMILY != "recsys":
        raise SystemExit(f"{cfg.name} ({mod.FAMILY}) has no serving path")
    from repro.models import recsys

    params = recsys.init_params(cfg, jax.random.PRNGKey(0))
    if cfg.kind in ("sasrec", "dien"):
        from repro.serve import RecsysMIPSRoute

        probe = None
        if args.ladder:
            probe = rng.integers(-1, cfg.item_vocab, (32, cfg.seq_len)).astype(
                np.int32
            )
        route = RecsysMIPSRoute(cfg, params, k=args.k, probe_hists=probe)
        payload = lambda: rng.integers(
            -1, cfg.item_vocab, (cfg.seq_len,)
        ).astype(np.int32)
        return cfg, route, payload
    from repro.serve import DenseCandidateRoute

    route = DenseCandidateRoute(
        cfg, params, candidates=np.arange(500, dtype=np.int32), k=args.k
    )
    if cfg.kind == "wide_deep":
        payload = lambda: (
            rng.integers(0, 10**6, (cfg.n_sparse,)).astype(np.int32),
            rng.normal(size=(cfg.n_dense,)).astype(np.float32),
        )
    else:
        payload = lambda: rng.integers(-1, cfg.item_vocab, (cfg.seq_len,)).astype(
            np.int32
        )
    return cfg, route, payload


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--scale", choices=("smoke", "full"), default="smoke",
                    help="smoke: SMOKE_CONFIG; full: the published CONFIG")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--qps", type=float, default=0.0,
                    help="offered arrival rate (0 = all at t=0, closed loop)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--k", type=int, default=10, help="top-k per request")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=8)
    ap.add_argument("--ladder", action="store_true",
                    help="arm the retrieval degradation ladder (MIPS archs)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through the cluster dispatcher with N "
                         "replicas (1 = single engine)")
    ap.add_argument("--chaos", action="store_true",
                    help="script a replica death mid-traffic (needs "
                         "--replicas >= 2)")
    ap.add_argument("--obs-dir", default=None,
                    help="write metrics.jsonl + trace.json here")
    return ap


def main() -> None:
    from repro.obs.report import percentile
    from repro.obs.run import ObsConfig, ObsRun
    from repro.serve import CoalescePolicy, ServingEngine

    args = make_parser().parse_args()
    enable_compile_cache()
    if args.replicas < 1:
        raise SystemExit("--replicas must be >= 1")
    if args.chaos and args.replicas < 2:
        raise SystemExit("--chaos needs --replicas >= 2 (survivors must exist)")
    mod = get_arch(args.arch)
    rng = np.random.default_rng(0)
    obs_cfg = ObsConfig(run_dir=args.obs_dir, drift=None) if args.obs_dir else None
    with ObsRun(obs_cfg) as run:
        cfg, route, payload = build_route(mod, args, rng)
        health = None
        if args.ladder and hasattr(route, "probe"):
            from repro.health.index_health import IndexHealthConfig

            health = IndexHealthConfig(probe_every=4, recall_floor=0.5)
        coalesce = CoalescePolicy(
            max_batch=args.max_batch, max_wait_s=args.max_wait_ms / 1e3
        )
        if args.replicas > 1:
            _serve_cluster(args, mod, cfg, route, payload, coalesce, health,
                           run, rng, percentile)
        else:
            engine = ServingEngine(route, coalesce, bus=run.bus, health=health)
            engine.warmup()
            for i in range(args.requests):
                engine.submit(payload(), arrival=i / args.qps if args.qps else 0.0)
            records = engine.drain()
            lats = [r.latency for r in records]
            makespan = max(r.finish for r in records) - records[0].arrival
            run.bus.log(
                f"{cfg.name}: {len(records)} requests in {engine.batches} "
                f"batches (occupancy {engine.occupancy():.2f}) — p50 "
                f"{percentile(lats, 50) * 1e3:.1f} ms, p99 "
                f"{percentile(lats, 99) * 1e3:.1f} ms, "
                f"{len(records) / makespan:.1f} req/s"
            )
            run.bus.drain()
    if args.obs_dir:
        print(f"obs artifacts in {args.obs_dir}")


def _serve_cluster(args, mod, cfg, first_route, payload, coalesce, health,
                   run, rng, percentile) -> None:
    """The --replicas > 1 path: N route copies behind the dispatcher."""
    from repro.health.faults import ReplicaFaultPlan
    from repro.serve import Dispatcher, DispatchPolicy

    routes = [first_route]
    for _ in range(args.replicas - 1):
        _, route, _ = build_route(mod, args, rng)
        routes.append(route)
    # kill replica 1 at its FIRST dispatch — least-loaded routing
    # guarantees it gets one (measured service times make later dispatch
    # counts run-dependent) — and mark dead on the first failure: the
    # CLI drill is a demonstration, not a flap-tolerance test
    plan = ReplicaFaultPlan(die=((1, 1),)) if args.chaos else None
    policy = DispatchPolicy(max_failures=1) if args.chaos else DispatchPolicy()
    disp = Dispatcher(
        routes, coalesce, policy, bus=run.bus, health=health,
        fault_plan=plan,
    )
    disp.warmup()
    for i in range(args.requests):
        disp.submit(payload(), arrival=i / args.qps if args.qps else 0.0)
    res = disp.drain()
    lats = disp.latencies()
    split = ", ".join(
        f"r{r['replica']}:{r['requests']}{'' if r['alive'] else ' (dead)'}"
        for r in disp.per_replica()
    )
    run.bus.log(
        f"{cfg.name} x{args.replicas} replicas"
        f"{' [chaos: kill replica 1]' if args.chaos else ''}: "
        f"{len(res)} answered / {len(res.unanswered)} unanswered — p50 "
        f"{percentile(lats, 50) * 1e3:.1f} ms, p99 "
        f"{percentile(lats, 99) * 1e3:.1f} ms; retries "
        f"{disp.bus.total('serve_retries'):g}, deaths "
        f"{disp.bus.total('serve_replica_deaths'):g}, rebalances "
        f"{disp.bus.total('serve_rebalances'):g}; load [{split}]"
    )
    run.bus.drain()
    if args.chaos and res.unanswered:
        raise SystemExit(
            f"chaos run dropped {len(res.unanswered)} requests — the "
            "re-queue path must answer everything with survivors up"
        )


if __name__ == "__main__":
    main()
