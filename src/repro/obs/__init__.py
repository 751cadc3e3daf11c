"""repro.obs — the telemetry spine: metrics bus, phase tracing,
roofline-drift monitoring, and run reports.

One observability path for trainer, dist, health, index-maintenance,
benchmark and serving code:

  bus      `MetricsBus` — typed counters/gauges/timings/events with
           labels; zero-host-sync (device scalars recorded as futures,
           drained after `block_until_ready`); pluggable sinks
           (in-memory ring, JSONL file, human log lines)
  trace    `span("dispatch", step=3)` phase spans -> Chrome-trace JSON
           and the jax.profiler trace, plus config-gated profiler hooks
  drift    `DriftMonitor` — measured step time vs the analytic roofline
           models, EMA ratio + hysteresis warnings (the autotuner's
           feedback signal)
  schema   THE declared history schema (`validate_history` rejects
           undeclared keys)
  report   `python -m repro.obs.report <run_dir>` renders the JSONL
           stream into a markdown run report

`ObsRun`/`ObsConfig` (repro.obs.run) bundle all of it for one run; the
trainer takes `TrainerConfig(obs=ObsConfig(...))`.
"""
from repro.obs.bus import MetricsBus
from repro.obs.drift import DriftConfig, DriftMonitor
from repro.obs.run import ObsConfig, ObsRun
from repro.obs.schema import HISTORY_SCHEMA, validate_history
from repro.obs.sinks import HumanLogSink, JSONLSink, RingSink
from repro.obs.trace import Tracer, span, tracing

__all__ = [
    "DriftConfig",
    "DriftMonitor",
    "HISTORY_SCHEMA",
    "HumanLogSink",
    "JSONLSink",
    "MetricsBus",
    "ObsConfig",
    "ObsRun",
    "RingSink",
    "Tracer",
    "span",
    "tracing",
    "validate_history",
]
