"""ObsRun: one training/serving run's telemetry, assembled from config.

`ObsConfig` is the single knob surface; `ObsRun` owns the bus, the
sinks, the ambient tracer, the optional jax.profiler session and the
roofline-drift monitor for the duration of one run. The trainer enters
it around `train()` (`with ObsRun(...) as run:`), records through
`run.bus`, and takes the schema-shaped history back from
`run.history()` at the end — the bus's ring sink IS the history's
backing store.

With ``run_dir`` set the run leaves artifacts behind:

    <run_dir>/metrics.jsonl   every drained record, one JSON line each
                              (appended across train() calls of one run)
    <run_dir>/trace.json      Chrome-trace phase spans (chrome://tracing)
    <run_dir>/jaxprof/        jax.profiler trace (jax_profiler=True only)

`python -m repro.obs.report <run_dir>` renders the JSONL stream into a
markdown run report.
"""
from __future__ import annotations

import dataclasses
import os

from repro.obs import trace as trace_mod
from repro.obs.bus import MetricsBus
from repro.obs.drift import DriftConfig, DriftMonitor
from repro.obs.schema import history_from_records
from repro.obs.sinks import HumanLogSink, JSONLSink, RingSink

__all__ = ["ObsConfig", "ObsRun"]

TRACE_FILE = "trace.json"
METRICS_FILE = "metrics.jsonl"


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Knobs of the telemetry layer (`TrainerConfig.obs`).

    run_dir         directory for run artifacts (metrics.jsonl,
                    trace.json, jaxprof/); None keeps telemetry
                    in-memory only (bus + history, no files)
    jsonl           write the JSONL record stream (needs run_dir)
    trace           record phase spans into a Chrome trace (written to
                    run_dir when set; span recording itself is
                    in-memory: one list append and one profiler
                    annotation per phase)
    jax_profiler    start a jax.profiler trace into run_dir/jaxprof —
                    device-level timelines, strictly config-gated
    drift           DriftConfig arming the roofline-drift monitor
                    (None disables; needs an analytic prediction, so
                    plans without one leave it off)
    log_timestamps  prefix human log lines with wall-clock stamps
                    (default off: output identical to the bare prints
                    this sink replaced)
    ring_capacity   bound the in-memory record ring (None = unbounded,
                    required for a faithful history view)
    """

    run_dir: str | None = None
    jsonl: bool = True
    trace: bool = True
    jax_profiler: bool = False
    drift: DriftConfig | None = dataclasses.field(default_factory=DriftConfig)
    log_timestamps: bool = False
    ring_capacity: int | None = None


class ObsRun:
    """Context manager owning one run's telemetry plumbing. Usable with
    cfg=None: the bus + ring + human log sink still run (that is how the
    trainer backs `history` and its log lines with zero config), just
    with no files, no tracer, no drift monitor."""

    def __init__(
        self,
        cfg: ObsConfig | None = None,
        *,
        predicted_step_s: float | None = None,
        log_stream=None,
    ):
        self.cfg = cfg
        self.ring = RingSink(cfg.ring_capacity if cfg is not None else None)
        sinks: list = [self.ring]
        self.run_dir = cfg.run_dir if cfg is not None else None
        if self.run_dir:
            os.makedirs(self.run_dir, exist_ok=True)
            if cfg.jsonl:
                sinks.append(JSONLSink(os.path.join(self.run_dir, METRICS_FILE)))
        sinks.append(HumanLogSink(
            stream=log_stream,
            timestamps=cfg.log_timestamps if cfg is not None else False,
        ))
        self.bus = MetricsBus(sinks)
        self.tracer = (
            trace_mod.Tracer() if cfg is not None and cfg.trace else None
        )
        self.drift: DriftMonitor | None = None
        if cfg is not None and cfg.drift is not None and predicted_step_s:
            self.drift = DriftMonitor(predicted_step_s, cfg.drift)
        self._profiling = False
        self._tracing = None

    # -- lifecycle ------------------------------------------------------
    def __enter__(self) -> "ObsRun":
        if self.tracer is not None:
            # restores, on exit, whatever tracer was installed before
            self._tracing = trace_mod.tracing(self.tracer)
            self._tracing.__enter__()
        if self.cfg is not None and self.cfg.jax_profiler and self.run_dir:
            self._profiling = trace_mod.start_jax_profiler(
                os.path.join(self.run_dir, "jaxprof")
            )
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._tracing is not None:
            self._tracing.__exit__(None, None, None)
            self._tracing = None
            if self.run_dir:
                self.tracer.write(os.path.join(self.run_dir, TRACE_FILE))
        if self._profiling:
            trace_mod.stop_jax_profiler()
            self._profiling = False
        self.bus.close()

    # -- per-step hooks -------------------------------------------------
    def observe_step_time(self, seconds: float, step: int) -> None:
        """Record the step wall time and feed the drift monitor: the
        EMA ratio lands in the `drift` series, band excursions in
        `drift_events` (one warning per excursion — hysteresis in
        `DriftMonitor`)."""
        self.bus.timing("step_time", seconds, step=step)
        if self.drift is None:
            return
        warning = self.drift.observe(seconds)
        if self.drift.ema is not None:
            self.bus.gauge("drift", self.drift.ema, step=step)
        if warning is not None:
            self.bus.event("drift_events", dict(warning, step=step), step=step)

    # -- the history view ----------------------------------------------
    def history(self) -> dict:
        """The schema-shaped history dict, folded from the ring's
        drained records (drain first)."""
        self.bus.drain()
        return history_from_records(self.ring.records)
