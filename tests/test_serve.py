"""Continuous-batching serving engine: coalescer policy, batched-vs-
sequential parity, the LM decode route, the mid-run fault drill, and
the serving section of the obs report."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.serve import (
    CoalescePolicy,
    RecsysMIPSRoute,
    ServingEngine,
    next_batch,
    pad_payloads,
)

# ---------------------------------------------------------------------------
# coalescer (pure host logic — no model)
# ---------------------------------------------------------------------------


def test_next_batch_full_trigger_fires_immediately():
    pol = CoalescePolicy(max_batch=4, max_wait_s=1.0)
    size, launch = next_batch([0.0, 0.1, 0.2, 0.3, 0.4], 0.0, pol)
    # 4th arrival fills the batch long before the wait cap
    assert (size, launch) == (4, 0.3)


def test_next_batch_wait_cap_launches_short_batch():
    pol = CoalescePolicy(max_batch=8, max_wait_s=0.005)
    size, launch = next_batch([0.0, 0.001, 0.1], 0.0, pol)
    # a lull: the oldest request waits 5ms then launches with one rider
    assert size == 2
    assert launch == pytest.approx(0.005)


def test_next_batch_fills_while_engine_busy():
    pol = CoalescePolicy(max_batch=8, max_wait_s=0.001)
    arrivals = [0.0, 0.002, 0.004, 0.006, 0.008]
    # engine busy until t=0.01: everything already arrived joins
    size, launch = next_batch(arrivals, 0.01, pol)
    assert (size, launch) == (5, 0.01)


def test_next_batch_ragged_arrivals_fifo_order():
    pol = CoalescePolicy(max_batch=2, max_wait_s=2.0)
    arrivals = [0.0, 0.0, 0.0, 5.0]
    size, launch = next_batch(arrivals, 0.0, pol)
    assert (size, launch) == (2, 0.0)  # batch-full, oldest two first
    size, launch = next_batch(arrivals[2:], launch + 1.0, pol)
    assert size == 1  # the t=5 rider hasn't arrived by the wait cap
    assert launch == pytest.approx(2.0)


def test_next_batch_empty_queue_raises():
    with pytest.raises(ValueError):
        next_batch([], 0.0, CoalescePolicy())


def test_pad_payloads():
    pad = np.zeros((3,))
    out = pad_payloads([np.ones((3,))], 3, pad)
    assert len(out) == 3 and out[1] is pad
    with pytest.raises(ValueError):
        pad_payloads([pad] * 4, 3, pad)


def test_coalesce_policy_validates():
    with pytest.raises(ValueError):
        CoalescePolicy(max_batch=0)
    with pytest.raises(ValueError):
        CoalescePolicy(max_wait_s=-1.0)


# ---------------------------------------------------------------------------
# engine + recsys route
# ---------------------------------------------------------------------------


def _sasrec():
    cfg = get_arch("sasrec").SMOKE_CONFIG
    from repro.models import recsys

    params = recsys.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _hists(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(-1, cfg.item_vocab, (cfg.seq_len,)).astype(np.int32)
        for _ in range(n)
    ]


def _run_all(engine, payloads, arrivals):
    for p, a in zip(payloads, arrivals):
        engine.submit(p, a)
    return engine.drain()


def test_batched_matches_sequential():
    cfg, params = _sasrec()
    payloads = _hists(cfg, 10)
    results = {}
    for mb in (1, 4):
        eng = ServingEngine(
            RecsysMIPSRoute(cfg, params, k=8),
            CoalescePolicy(max_batch=mb, max_wait_s=0.001),
        )
        eng.warmup()
        recs = _run_all(eng, payloads, [0.0] * len(payloads))
        assert [r.rid for r in recs] == list(range(10))  # FIFO answers
        results[mb] = [r.result[0] for r in recs]
    for seq_ids, bat_ids in zip(results[1], results[4]):
        np.testing.assert_array_equal(seq_ids, bat_ids)


def test_engine_records_and_occupancy():
    cfg, params = _sasrec()
    eng = ServingEngine(
        RecsysMIPSRoute(cfg, params, k=4),
        CoalescePolicy(max_batch=4, max_wait_s=0.5),
    )
    eng.warmup()
    recs = _run_all(eng, _hists(cfg, 8), [0.0] * 8)
    assert len(recs) == 8 and eng.batches == 2
    assert eng.occupancy() == pytest.approx(4.0)
    for r in recs:
        assert r.finish >= r.launch >= r.arrival
        assert r.latency >= r.queue_wait >= 0.0
    # the second batch launches only after the first frees the engine
    assert recs[4].launch >= recs[0].finish


def test_serve_batch_spans_nest_inside_the_batch():
    from repro.obs.trace import Tracer, tracing
    from repro.serve.coalescer import Request

    cfg, params = _sasrec()
    eng = ServingEngine(
        RecsysMIPSRoute(cfg, params, k=4),
        CoalescePolicy(max_batch=4, max_wait_s=0.5),
    )
    eng.warmup()
    batch = [Request(rid=r, payload=h, arrival=0.0)
             for r, h in enumerate(_hists(cfg, 3))]
    tr = Tracer()
    with tracing(tr):
        assert len(eng.serve_batch(batch)) == 3
    ev = {e["name"]: e for e in tr.events}
    outer = ev["serve_batch"]
    assert outer["args"] == {"batch": 0, "n": 3}
    phases = ["serve_prepare", "serve_run", "serve_wait", "serve_finalize",
              "serve_record"]
    assert set(ev) == {"serve_batch", *phases}
    for a, b in zip(phases, phases[1:]):
        assert ev[a]["ts"] + ev[a]["dur"] <= ev[b]["ts"]
    for name in phases:
        e = ev[name]
        assert e["args"] == {"batch": 0}
        assert outer["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"]


def test_query_exact_is_exact_top_k_of_the_user_tower():
    """The planner's exact-fallback query — the reference a recall check
    holds the live index to — is exact top-k over the whole catalog,
    and leaves the live (IVF) plan in place."""
    from repro.mips import topk_exact

    cfg, params = _sasrec()
    route = RecsysMIPSRoute(cfg, params, k=8)
    planner = route.planner
    x = jax.numpy.asarray(np.stack(_hists(cfg, 4)))
    got = planner.query_exact(x)
    h = planner.policy.user_embedding(params, x)
    want = topk_exact(h, params["items"], 8)
    np.testing.assert_array_equal(np.asarray(got.indices), np.asarray(want.indices))
    assert not planner.degraded and planner.fallback_plan.degraded


def test_query_reference_matches_the_kernel_route():
    """The planner's jnp IVF reference probes the same lists as the
    `ivf_topk` kernel route at the same n_probe: same ids, same scores."""
    cfg, params = _sasrec()
    planner = RecsysMIPSRoute(cfg, params, k=8).planner
    x = jax.numpy.asarray(np.stack(_hists(cfg, 4)))
    got, want = planner.query(x), planner.query_reference(x)
    np.testing.assert_array_equal(np.asarray(got.indices), np.asarray(want.indices))
    np.testing.assert_allclose(np.asarray(got.scores), np.asarray(want.scores),
                               rtol=1e-5, atol=1e-6)


def test_submit_rejects_decreasing_arrivals():
    cfg, params = _sasrec()
    eng = ServingEngine(RecsysMIPSRoute(cfg, params, k=4))
    eng.submit(_hists(cfg, 1)[0], arrival=1.0)
    with pytest.raises(ValueError):
        eng.submit(_hists(cfg, 1)[0], arrival=0.5)


# ---------------------------------------------------------------------------
# LM decode route (next token through the query-only plan path)
# ---------------------------------------------------------------------------


def test_lm_return_hidden_consistent_with_logits():
    from repro.models import lm

    cfg = get_arch("gemma2-2b").SMOKE_CONFIG
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.arange(2 * 8, dtype=jnp.int32).reshape(2, 8) % cfg.vocab_size
    cache = lm.init_cache(cfg, 2, 8)
    logits, _ = lm.prefill(cfg, params, tokens, cache)
    hidden, _ = lm.prefill(cfg, params, tokens, cache, return_hidden=True)
    unembed = params.get("unembed", params["embed"])
    from repro.models.lm import softcap

    recon = softcap(hidden @ unembed.T, cfg.final_logit_softcap)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(recon), rtol=2e-2, atol=2e-2
    )
    # softcap is monotonic: the MIPS argmax IS the logits argmax
    np.testing.assert_array_equal(
        np.argmax(np.asarray(logits), -1), np.argmax(np.asarray(recon), -1)
    )


def test_lm_route_generates_batched():
    from repro.models import lm
    from repro.serve import LMGenerateRoute

    cfg = get_arch("gemma2-2b").SMOKE_CONFIG
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    route = LMGenerateRoute(
        cfg, params, prompt_len=6, gen_len=3, max_batch=2, top_k=4
    )
    eng = ServingEngine(route, CoalescePolicy(max_batch=2, max_wait_s=0.01))
    eng.warmup()
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
        for _ in range(3)
    ]
    recs = _run_all(eng, prompts, [0.0] * 3)
    assert len(recs) == 3
    for r in recs:
        assert len(r.result) == 3  # gen_len tokens
        assert all(0 <= t < cfg.vocab_size for t in r.result)


# ---------------------------------------------------------------------------
# fault drill: corrupt the served index mid-run, ladder to fallback
# ---------------------------------------------------------------------------


def test_fault_drill_walks_ladder_to_fallback():
    from repro.health.faults import corrupt_index_state
    from repro.health.index_health import IndexHealthConfig

    cfg, params = _sasrec()
    probe = np.stack(_hists(cfg, 8, seed=7))
    eng = ServingEngine(
        RecsysMIPSRoute(cfg, params, k=4, probe_hists=probe),
        CoalescePolicy(max_batch=4, max_wait_s=0.5),
        # the 1.01 floor judges every probe unhealthy — the ladder walk
        # is deterministic (the fault-injection convention)
        health=IndexHealthConfig(
            probe_every=1, probe_k=8, recall_floor=1.01, cooldown=0
        ),
    )
    eng.warmup()
    pre = _run_all(eng, _hists(cfg, 4), [0.0] * 4)
    assert len(pre) == 4
    planner = eng.route.planner
    planner.index_state = corrupt_index_state(
        planner.index_state, jax.random.PRNGKey(1)
    )
    t0 = eng.free_at
    post = _run_all(eng, _hists(cfg, 12, seed=1), [t0] * 12)
    # every rung executed, in order, and the route ends on the exact
    # fallback — while every request kept answering
    actions = [h["action"] for h in eng.monitor.history if h["action"]]
    assert actions == ["compact", "rebuild", "fallback"]
    assert eng.route.degraded
    assert len(post) == 12 and len(eng.records) == 16
    assert all(np.all(np.asarray(r.result[0]) >= 0) for r in post)


def test_live_tile_share_gauge_at_build_and_heal_never_per_batch():
    """The engine emits its route's `ivf.live_tile_share` at construction
    and after each ladder compaction or rebuild — the planner read it
    from the lists then — and not once per batch."""
    from repro.health.index_health import IndexHealthConfig
    from repro.kernels.ivf_topk import live_tile_share
    from repro.obs.bus import MetricsBus
    from repro.obs.sinks import RingSink

    cfg, params = _sasrec()
    ring = RingSink()
    eng = ServingEngine(
        RecsysMIPSRoute(cfg, params, k=4, probe_hists=np.stack(_hists(cfg, 8))),
        CoalescePolicy(max_batch=2, max_wait_s=0.5),
        bus=MetricsBus([ring]),
        health=IndexHealthConfig(
            probe_every=2, probe_k=8, recall_floor=1.01, cooldown=0
        ),
    )
    planner = eng.route.planner
    built = planner.live_tile_share
    assert built == live_tile_share(planner.index_state.lists)
    eng.warmup()
    _run_all(eng, _hists(cfg, 16), [0.0] * 16)
    assert eng.batches == 8
    gauges = [r for r in ring.records if r["name"] == "ivf.live_tile_share"]
    # construction (batch 0), the compact (batch 2), the rebuild (batch 4);
    # the fallback (batch 6) leaves the index
    assert [r["step"] for r in gauges] == [0, 2, 4]
    assert gauges[0]["value"] == built
    assert all(0 < r["value"] <= 1 for r in gauges)


# ---------------------------------------------------------------------------
# obs: the serving section of the run report
# ---------------------------------------------------------------------------


def test_serve_report_renders_request_timings(tmp_path):
    from repro.obs.report import load_records, render
    from repro.obs.run import ObsConfig, ObsRun

    cfg, params = _sasrec()
    run_dir = str(tmp_path / "serve_run")
    with ObsRun(ObsConfig(run_dir=run_dir, drift=None)) as run:
        eng = ServingEngine(
            RecsysMIPSRoute(cfg, params, k=4),
            CoalescePolicy(max_batch=4, max_wait_s=0.5),
            bus=run.bus,
        )
        eng.warmup()
        _run_all(eng, _hists(cfg, 8), [0.0] * 8)
        run.bus.drain()
    text = render(load_records(run_dir))
    assert "## Serving" in text
    assert "8 requests in 2 batches" in text
    for row in ("e2e latency", "queue wait", "batch service"):
        assert row in text
