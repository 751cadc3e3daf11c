"""Telemetry spine (repro.obs) tests.

The contract under test: recording NEVER reads a device value (futures
materialise only at drain, after the owner's block), the trainer's
history keeps its exact shape while being backed by the bus, the human
log lines are byte-identical to the prints they replaced, telemetry is
a bitwise no-op on the trajectory, the drift monitor warns exactly once
per band excursion, and the declared history schema rejects undeclared
keys so new metrics can't rot silently.
"""
import io
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fopo import FOPOConfig
from repro.data import SyntheticConfig, generate_sessions
from repro.health import FaultPlan, HealthConfig
from repro.health.guard import ESS_COLLAPSE, verdict_record
from repro.obs import (
    HISTORY_SCHEMA,
    DriftConfig,
    DriftMonitor,
    HumanLogSink,
    JSONLSink,
    MetricsBus,
    ObsConfig,
    ObsRun,
    RingSink,
    Tracer,
    span,
    tracing,
    validate_history,
)
from repro.obs import trace as trace_mod
from repro.obs.report import percentile, render_run
from repro.obs.schema import empty_history, history_from_records
from repro.obs.sinks import format_rollback_line, format_train_line
from repro.train import FOPOTrainer, TrainerConfig


# ---------------------------------------------------------------------------
# shared fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ds():
    full = generate_sessions(SyntheticConfig(
        num_items=300, num_users=200, embed_dim=16, session_len=8, seed=0
    ))
    train, _ = full.split(0.85, seed=0)
    return train


def _trainer(ds, *, obs=None, health=None, fault=None, steps=8, seed=0):
    fopo = FOPOConfig(
        num_items=300, num_samples=32, top_k=16, epsilon=0.8,
        retriever="streaming",
    )
    cfg = TrainerConfig(
        estimator="fopo", fopo=fopo, batch_size=16, learning_rate=1e-3,
        num_steps=steps, checkpoint_every=0, seed=seed, health=health,
        obs=obs,
    )
    return FOPOTrainer(cfg, ds, fault_plan=fault)


# ---------------------------------------------------------------------------
# the metrics bus
# ---------------------------------------------------------------------------

def test_bus_records_kinds_and_totals():
    ring = RingSink()
    bus = MetricsBus([ring])
    bus.counter("c", 2.0)
    bus.counter("c", 3.0, step=4)
    bus.gauge("g", 1.5, step=1, route="x")
    bus.timing("t", 0.25)
    bus.event("e", {"a": 1})
    # nothing reaches a sink before drain
    assert bus.pending == 5 and len(ring.records) == 0
    assert bus.drain() == 5 and bus.pending == 0
    assert [r["kind"] for r in ring.records] == [
        "counter", "counter", "gauge", "timing", "event"
    ]
    assert bus.total("c") == 5.0 and bus.total("never") == 0.0
    g = ring.records[2]
    assert g["step"] == 1 and g["labels"] == {"route": "x"}


class _Probe:
    """float() tripwire: materialising before the owner's block (i.e. at
    record time) is exactly the host sync the bus must never add."""

    def __init__(self):
        self.allowed = False

    def __float__(self):
        if not self.allowed:
            raise AssertionError("device value read at record time")
        return 7.0


def test_bus_defers_value_reads_to_drain():
    ring = RingSink()
    bus = MetricsBus([ring])
    probe = _Probe()
    bus.gauge("loss", probe, step=0)  # must not call float() here
    assert bus.pending == 1
    probe.allowed = True  # "block_until_ready happened"
    bus.drain()
    assert ring.records[0]["value"] == 7.0


def test_bus_recording_keeps_single_trace():
    """Recording in-flight device scalars every step neither retraces
    nor blocks the jitted step (the test_refresh cache-size trick)."""
    bus = MetricsBus([RingSink()])

    @jax.jit
    def step(x):
        return x * 2.0, jnp.sum(x)

    x = jnp.ones((8,))
    for i in range(5):
        x, s = step(x)
        bus.gauge("s", s, step=i)  # the future, recorded in flight
    jax.block_until_ready(x)
    assert step._cache_size() == 1
    bus.drain()


def test_ring_capacity_bounds():
    ring = RingSink(capacity=3)
    bus = MetricsBus([ring])
    for i in range(10):
        bus.gauge("g", float(i))
    bus.drain()
    assert [r["value"] for r in ring.records] == [7.0, 8.0, 9.0]


def test_jsonl_sink_roundtrip_and_append(tmp_path):
    path = str(tmp_path / "m.jsonl")
    sink = JSONLSink(path)
    sink.emit({"t": 0, "kind": "event", "name": "e", "value": {"x": 1}})
    sink.emit({"t": 0, "kind": "event", "name": "bad", "value": object()})
    sink.close()
    lines = [json.loads(line) for line in open(path)]
    assert lines[0]["value"] == {"x": 1}
    assert isinstance(lines[1]["value"], str)  # repr fallback, not a crash
    # append mode: a second train() call on the same run_dir extends
    sink2 = JSONLSink(path)
    sink2.emit({"t": 1, "kind": "gauge", "name": "g", "value": 2.0})
    sink2.close()
    assert len(open(path).readlines()) == 3


def test_jsonl_sink_retries_transient_write_failures(tmp_path):
    # two injected failures, then success: the record must land after
    # reopen+retry — a disk hiccup must not kill a serving process
    path = str(tmp_path / "m.jsonl")
    sink = JSONLSink(path, retries=3, backoff=0.0)
    fails = [2]
    real_write = sink._f.write

    class Flaky:
        def write(self, s):
            if fails[0] > 0:
                fails[0] -= 1
                raise OSError("injected transient IO failure")
            return real_write(s)

        def close(self):
            pass

        def flush(self):
            pass

    sink._f = Flaky()
    sink.emit({"t": 0, "kind": "gauge", "name": "g", "value": 1.0})
    sink.close()
    lines = [json.loads(line) for line in open(path)]
    assert lines and lines[-1]["value"] == 1.0


def test_jsonl_sink_disarms_after_exhausted_retries(tmp_path, capsys):
    # persistent failure: the sink disarms itself (emits become no-ops)
    # instead of raising into the serving loop
    path = str(tmp_path / "m.jsonl")
    sink = JSONLSink(path, retries=2, backoff=0.0)

    class Dead:
        def write(self, s):
            raise OSError("disk on fire")

        def close(self):
            raise OSError("still on fire")

        def flush(self):
            pass

    sink._f = Dead()
    real_reopen = sink._reopen
    sink._reopen = lambda: None  # reopen keeps handing back the dead handle
    sink.emit({"t": 0, "kind": "gauge", "name": "g", "value": 1.0})
    assert sink._f is None
    assert "disarmed" in capsys.readouterr().err
    sink.emit({"t": 0, "kind": "gauge", "name": "g", "value": 2.0})  # no-op
    sink.close()  # and close stays safe
    del real_reopen


def test_jsonl_sink_backoff_sleep_is_bounded(tmp_path, monkeypatch, capsys):
    # the sink sits on the serving drain path: a persistently failing
    # disk must not stall a batch interval — total ladder sleep is
    # capped at max_sleep_s, then the sink disarms
    import repro.obs.sinks as sinks_mod

    slept = []
    monkeypatch.setattr(sinks_mod.time, "sleep", lambda s: slept.append(s))
    path = str(tmp_path / "m.jsonl")
    sink = JSONLSink(path, retries=8, backoff=0.05, max_sleep_s=0.08)

    class Dead:
        def write(self, s):
            raise OSError("disk on fire")

        def close(self):
            pass

        def flush(self):
            pass

    sink._f = Dead()
    sink._reopen = lambda: None
    sink.emit({"t": 0, "kind": "gauge", "name": "g", "value": 1.0})
    assert sink._f is None  # still disarms
    assert sum(slept) <= 0.08 + 1e-9
    capsys.readouterr()


def test_human_log_sink_prints_only_log_records():
    out = io.StringIO()
    sink = HumanLogSink(stream=out)
    sink.emit({"t": 0.0, "kind": "gauge", "name": "loss", "value": 1.0})
    sink.emit({"t": 0.0, "kind": "event", "name": "log", "value": "hello"})
    assert out.getvalue() == "hello\n"  # verbatim, no stamp by default
    stamped = io.StringIO()
    HumanLogSink(stream=stamped, timestamps=True).emit(
        {"t": 0.0, "kind": "event", "name": "log", "value": "hello"}
    )
    assert stamped.getvalue().endswith(" hello\n")
    assert len(stamped.getvalue()) > len("hello\n")


def test_format_helpers_match_legacy_print_strings():
    aux = {"ess": 25.44, "rbar": 0.0143, "max_wbar": 0.0621}
    step, loss = 40, -0.0123456
    legacy = f"step {step}: loss={loss:+.5f}"
    legacy += (
        f" ess={aux['ess']:.1f} rbar={aux['rbar']:+.4f}"
        f" max_wbar={aux['max_wbar']:.3f}"
    )
    assert format_train_line(step, loss, aux) == legacy
    assert (
        format_train_line(step, loss, aux, ("ess_collapse",), True)
        == legacy + " health=ess_collapse [degraded:exact]"
    )
    assert format_train_line(3, 0.5) == "step 3: loss=+0.50000"
    assert format_rollback_line(7, 4, 2) == "step 7: ROLLBACK to 4 (restart #2)"


# ---------------------------------------------------------------------------
# phase tracing
# ---------------------------------------------------------------------------

def test_tracer_spans_nest_and_write(tmp_path):
    tr = Tracer()
    with tracing(tr):
        with span("outer", step=1):
            with span("inner"):
                pass
    # complete events append at close: inner first, outer envelops it
    assert [e["name"] for e in tr.events] == ["inner", "outer"]
    inner, outer = tr.events
    assert outer["dur"] >= inner["dur"]
    assert all(e["ph"] == "X" for e in tr.events)
    assert outer["args"] == {"step": 1}
    doc = json.load(open(tr.write(str(tmp_path / "trace.json"))))
    assert {e["name"] for e in doc["traceEvents"]} == {"inner", "outer"}


def test_span_is_noop_without_tracer():
    assert trace_mod.current() is None
    with span("phantom"):  # must not raise, must not record anywhere
        pass
    assert trace_mod.current() is None


def test_span_without_tracer_never_reaches_the_profiler(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("span() reached jax.profiler with no tracer")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    with span("phantom", step=3):
        pass


def test_span_enters_a_profiler_annotation_with_its_args(monkeypatch):
    entered = []

    class Annotation:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            entered.append((self.name, self.kw))

        def __exit__(self, *exc):
            entered.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tr = Tracer()
    with tracing(tr):
        with span("dispatch", step=7):
            assert entered == [("repro.dispatch", {"step": 7})]
    assert entered[-1] == ("exit", "repro.dispatch")
    assert tr.events[0]["name"] == "dispatch"
    assert tr.events[0]["args"] == {"step": 7}


def test_obsrun_restores_the_tracer_it_found(tmp_path):
    outer = Tracer()
    with tracing(outer):
        with ObsRun(ObsConfig(run_dir=str(tmp_path / "run"), drift=None)) as run:
            assert trace_mod.current() is run.tracer is not outer
            with span("inner"):
                pass
        assert trace_mod.current() is outer
        with span("after"):
            pass
    assert [e["name"] for e in outer.events] == ["after"]
    assert [e["name"] for e in run.tracer.events] == ["inner"]
    assert trace_mod.current() is None


def _children(events, parent):
    """The events that lie inside ``parent`` by ts/dur, itself excluded."""
    lo, hi = parent["ts"], parent["ts"] + parent["dur"]
    return [e for e in events if e is not parent
            and lo <= e["ts"] and e["ts"] + e["dur"] <= hi]


def test_trainer_spans_nest_by_step(ds):
    tr = Tracer()
    with tracing(tr):
        _trainer(ds).train(2)
    ev = tr.events
    (train,) = [e for e in ev if e["name"] == "train"]
    steps = [e for e in ev if e["name"] == "train_step"]
    assert [e["args"]["step"] for e in steps] == [0, 1]
    assert len(_children(ev, train)) == len(ev) - 1
    for st in steps:
        inside = _children(ev, st)
        names = {e["name"] for e in inside}
        assert {"next_batch", "dispatch", "drain", "record"} <= names
        assert "train_step" not in names
        for e in inside:
            if e["name"] in ("next_batch", "dispatch", "drain", "record"):
                assert e["args"]["step"] == st["args"]["step"]
    # the phases run in order, one after another
    first = sorted(
        (e for e in _children(ev, steps[0])
         if e["name"] in ("next_batch", "dispatch", "drain", "record")),
        key=lambda e: e["ts"],
    )
    assert [e["name"] for e in first] == ["next_batch", "dispatch", "drain", "record"]
    for a, b in zip(first, first[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]


# ---------------------------------------------------------------------------
# roofline-drift monitor
# ---------------------------------------------------------------------------

def _drift_cfg(**kw):
    base = dict(band=0.5, ema_decay=0.5, calibration_steps=2,
                skip_steps=0, rearm_frac=0.6)
    base.update(kw)
    return DriftConfig(**base)


def test_drift_exactly_one_warning_per_excursion():
    m = DriftMonitor(1.0, _drift_cfg())
    assert m.observe(1.0) is None and m.observe(1.0) is None  # calibration
    # slow excursion: only the band crossing warns, staying out is quiet
    fired = [w for w in (m.observe(4.0) for _ in range(6)) if w]
    assert len(fired) == 1
    assert fired[0]["direction"] == "slow"
    assert fired[0]["event"] == "roofline_drift"
    # back inside the re-arm band: silent, but the monitor re-arms
    assert all(m.observe(1.0) is None for _ in range(10))
    # fast excursion fires exactly once again
    fired2 = [w for w in (m.observe(0.05) for _ in range(6)) if w]
    assert len(fired2) == 1 and fired2[0]["direction"] == "fast"
    assert m.warnings == 2


def test_drift_hysteresis_no_spam_at_band_edge():
    """A ratio hovering just outside the band after the first crossing
    must not re-warn until it first re-enters the re-arm band."""
    m = DriftMonitor(1.0, _drift_cfg(ema_decay=0.1))
    m.observe(1.0), m.observe(1.0)
    warns = sum(1 for _ in range(20) if m.observe(1.6))  # hovers ~1.6
    assert warns == 1
    # dip only into the outer band (not the re-arm band): still armed off
    m.observe(1.4)
    assert m.observe(1.7) is None


def test_drift_skip_steps_discards_compile_step():
    m = DriftMonitor(0.001, DriftConfig(calibration_steps=3, skip_steps=1))
    assert m.observe(50.0) is None  # jit-compile step: not even calibration
    for _ in range(3):
        m.observe(0.01)
    assert m.scale == pytest.approx(10.0)  # poison-free baseline
    m.observe(0.01)
    assert m.ema == pytest.approx(1.0)


def test_drift_config_validation():
    for bad in (
        dict(band=0.0), dict(ema_decay=1.0), dict(ema_decay=0.0),
        dict(calibration_steps=0), dict(rearm_frac=0.0),
        dict(rearm_frac=1.0), dict(skip_steps=-1),
    ):
        with pytest.raises(ValueError):
            DriftConfig(**bad)
    with pytest.raises(ValueError):
        DriftMonitor(0.0)


def test_predict_step_bytes_scales_with_shape():
    pytest.importorskip("benchmarks.roofline")
    from repro.core.plan import ExecutionPlan
    from repro.obs.drift import predict_step_bytes, predict_step_seconds

    plan = ExecutionPlan.resolve(FOPOConfig(
        num_items=500, num_samples=32, top_k=16, epsilon=0.8,
        retriever="streaming",
    ))
    pred = predict_step_bytes(plan, 16, 8)
    assert pred is not None and pred["total_bytes"] > 0
    assert pred["total_bytes"] == (
        pred["snis_bytes"] + pred["sampler_bytes"]
        + pred["retrieval_bytes"] + pred["comms_bytes"]
    )
    assert predict_step_seconds(plan, 16, 8) > 0
    # the scaling is the signal: a bigger batch must predict more bytes
    assert predict_step_bytes(plan, 32, 8)["total_bytes"] > pred["total_bytes"]


# ---------------------------------------------------------------------------
# the declared history schema
# ---------------------------------------------------------------------------

def test_validate_history_rejects_undeclared_keys():
    h = empty_history()
    h["total_time"] = 0.0
    assert validate_history(h) is h  # declared shape passes, chains
    h["my_new_metric"] = []
    with pytest.raises(KeyError, match="my_new_metric"):
        validate_history(h)


def test_history_from_records_folds_the_stream():
    recs = [
        {"kind": "gauge", "name": "loss", "value": 1.0},
        {"kind": "timing", "name": "step_time", "value": 0.1},
        {"kind": "event", "name": "reward", "value": {"step": 4, "value": 0.5}},
        {"kind": "event", "name": "health",
         "value": {"step": 1, "verdict": 8, "checks": ["ess_collapse"]}},
        {"kind": "gauge", "name": "bus_only_metric", "value": 9.0},
        {"kind": "event", "name": "log", "value": "step 1: ..."},
    ]
    h = history_from_records(recs)
    assert h["loss"] == [1.0] and h["step_time"] == [0.1]
    assert h["reward"] == [(4, 0.5)]  # the (step, value) tuple shape
    assert h["health"][0]["verdict"] == 8
    # bus-only records exist in the stream, not in the history view
    assert "bus_only_metric" not in h and "log" not in h
    assert set(h) <= set(HISTORY_SCHEMA)


def test_verdict_record_shape():
    assert verdict_record(5, ESS_COLLAPSE) == {
        "step": 5, "verdict": ESS_COLLAPSE, "checks": ["ess_collapse"],
    }


# ---------------------------------------------------------------------------
# trainer integration
# ---------------------------------------------------------------------------

def test_trainer_history_backed_by_bus(ds):
    hist = _trainer(ds).train(6)
    validate_history(hist)
    assert len(hist["loss"]) == len(hist["step_time"]) == len(hist["ess"]) == 6
    assert all(isinstance(v, float) for v in hist["loss"])  # drained, not futures
    assert hist["total_time"] > 0
    assert hist["health"] == [] and hist["events"] == []


def test_trainer_log_lines_byte_identical_to_legacy(ds, capsys):
    """Satellite (a): the obs human sink's cadence lines reproduce the
    old raw prints exactly — reconstructed here from the history values
    with the legacy f-strings."""
    hist = _trainer(ds).train(6, log_every=2)
    out = capsys.readouterr().out.splitlines()
    expect = [
        f"step {s}: loss={hist['loss'][s - 1]:+.5f}"
        f" ess={hist['ess'][s - 1]:.1f}"
        f" rbar={hist['rbar'][s - 1]:+.4f}"
        f" max_wbar={hist['max_wbar'][s - 1]:.3f}"
        for s in (2, 4, 6)
    ]
    assert out == expect


def test_obs_is_bitwise_noop_on_trajectory(ds, tmp_path):
    bare = _trainer(ds)
    instrumented = _trainer(ds, obs=ObsConfig(
        run_dir=str(tmp_path / "run"),
        drift=DriftConfig(calibration_steps=2),
    ))
    h_bare = bare.train(6)
    h_obs = instrumented.train(6)
    assert h_bare["loss"] == h_obs["loss"]
    for a, b in zip(
        jax.tree.leaves(bare.params), jax.tree.leaves(instrumented.params)
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_obsrun_without_config_still_backs_history():
    with ObsRun(None) as run:
        run.bus.gauge("loss", 1.0, step=0)
        run.observe_step_time(0.1, 0)
        hist = run.history()
    assert hist["loss"] == [1.0]
    assert hist["step_time"] == [0.1]
    assert hist["drift"] == []  # no prediction -> monitor off


def test_monitor_bus_binding_emits_gauges():
    from repro.health import IndexHealthConfig, IndexHealthMonitor

    ring = RingSink()
    bus = MetricsBus([ring])
    monitor = IndexHealthMonitor(IndexHealthConfig(
        probe_every=1, recall_floor=0.9, cooldown=0
    ))
    monitor.bind_bus(bus)
    assert monitor.observe(0.5, 0) == "compact"
    bus.drain()
    names = [r["name"] for r in ring.records]
    assert "index_probe_recall" in names
    assert "index_overflow_delta" in names
    assert bus.total("index_ladder_escalations") == 1.0


# ---------------------------------------------------------------------------
# run artifacts + report
# ---------------------------------------------------------------------------

def test_run_artifacts_and_report(ds, tmp_path):
    """The acceptance artifact path end to end: a guarded run with a
    scripted ESS collapse leaves a JSONL stream, a Chrome trace with the
    phase spans, and a rendered report carrying loss/ESS percentiles,
    the health event and the roofline-drift series."""
    run_dir = str(tmp_path / "run")
    trainer = _trainer(
        ds,
        obs=ObsConfig(run_dir=run_dir, drift=DriftConfig(calibration_steps=2)),
        health=HealthConfig(ess_floor=1.0),
        fault=FaultPlan(ess_collapse_at=(3,), ess_value=0.5),
        steps=10,
    )
    hist = trainer.train(10, log_every=5)
    assert any("ess_collapse" in e["checks"] for e in hist["health"])
    assert len(hist["drift"]) > 0

    records = [json.loads(line)
               for line in open(os.path.join(run_dir, "metrics.jsonl"))]
    assert any(r["name"] == "loss" for r in records)
    assert any(r["name"] == "health" for r in records)

    doc = json.load(open(os.path.join(run_dir, "trace.json")))
    names = {e["name"] for e in doc["traceEvents"]}
    # the trainer's runtime phases, one of each a step
    assert {"train_step", "next_batch", "dispatch", "drain", "record"} <= names

    text = open(render_run(run_dir)).read()
    assert "| loss |" in text and "| ess |" in text  # percentile rows
    assert "ess_collapse" in text  # the health timeline
    assert "drift_ratio" in text  # the plot-ready drift series


def test_percentile_nearest_rank():
    vs = [1.0, 2.0, 3.0, 4.0]
    assert percentile(vs, 0) == 1.0
    assert percentile(vs, 100) == 4.0
    assert percentile([5.0], 99) == 5.0


def test_bench_env_block(tmp_path, monkeypatch):
    """Satellite (b): every persisted BENCH artifact carries the env
    stamp (stack versions, backend, device/host counts, git SHA)."""
    common = pytest.importorskip("benchmarks.common")
    monkeypatch.setattr(common, "RESULTS_DIR", str(tmp_path))
    common.persist("unittest", [{"name": "x"}], 1.0)
    doc = json.load(open(tmp_path / "BENCH_unittest.json"))
    env = doc["env"]
    assert env["jax_version"] == jax.__version__
    assert env["backend"] and env["device_kind"]
    assert env["device_count"] >= 1 and env["host_count"] >= 1
    assert doc["rows"] == [{"name": "x"}] and doc["wall_s"] == 1.0
