"""Phase tracing: named host spans, kept as Chrome-trace JSON and
written into the `jax.profiler` trace.

``span("dispatch", step=3)`` wraps a host-side phase at a layer
boundary: the trainer's step and its children (`next_batch`,
`dispatch`, `index_refresh`, `drain`, `record`), the serving engine's
batch (`serve_prepare`, `serve_run`, `serve_wait`, `serve_finalize`,
`serve_record`), the IVF build (`index_build`), index maintenance,
checkpoint save/restore and health probes. Spans nest by ts/dur on one
thread track; ``args`` carry the step or batch id.

Each span is recorded twice:

* as a Chrome 'complete' event on the tracer, written to ``trace.json``
  by `ObsRun(run_dir=...)` (load it in chrome://tracing or Perfetto);
* as a `jax.profiler.TraceAnnotation` named ``repro.<name>`` with the
  args as its stats. Inside a profiler session it lands on the host
  plane, on the clock of the device's op events, so a device idle gap
  can be charged to the phase the host was in. Outside one it costs the
  annotation's construction.

The tracer is ambient: the `tracing()` context manager installs one
(restoring whatever was installed before), and `span()` is one global
read and a no-op when none is installed, touching neither the tracer
nor `jax.profiler` — so library code wraps phases unconditionally
without plumbing a tracer operand through every signature.

`start_jax_profiler(dir)` / `stop_jax_profiler()` wrap the device-level
profiler for runs that need XLA timelines, enabled by
`ObsConfig(jax_profiler=True)` only — never ambient.
"""
from __future__ import annotations

import json
import os
import time
from contextlib import contextmanager

from jax import profiler

__all__ = [
    "Tracer",
    "current",
    "span",
    "start_jax_profiler",
    "stop_jax_profiler",
    "tracing",
]

PROFILER_PREFIX = "repro."

_ACTIVE: "Tracer | None" = None


class Tracer:
    """Accumulates Chrome-trace 'complete' (ph=X) events, microsecond
    timestamps relative to construction."""

    def __init__(self):
        self.events: list[dict] = []
        self._t0 = time.perf_counter_ns()

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0) / 1e3

    @contextmanager
    def span(self, name: str, **args):
        ts = self._now_us()
        try:
            with profiler.TraceAnnotation(PROFILER_PREFIX + name, **args):
                yield
        finally:
            ev = {"name": name, "ph": "X", "ts": ts,
                  "dur": self._now_us() - ts, "pid": 0, "tid": 0}
            if args:
                ev["args"] = args
            self.events.append(ev)

    def write(self, path: str) -> str:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": self.events, "displayTimeUnit": "ms"}, f)
        return path


# ---------------------------------------------------------------------------
# the ambient tracer
# ---------------------------------------------------------------------------

def current() -> Tracer | None:
    return _ACTIVE


@contextmanager
def tracing(tracer: Tracer):
    """Install ``tracer`` for the duration of the block (restores the
    previous one — runs can nest, e.g. serve inside a test)."""
    global _ACTIVE
    prev, _ACTIVE = _ACTIVE, tracer
    try:
        yield tracer
    finally:
        _ACTIVE = prev


@contextmanager
def span(name: str, **args):
    """Record a span on the ambient tracer; a no-op when none is active
    (one global read — safe to leave in library hot paths)."""
    t = _ACTIVE
    if t is None:
        yield
        return
    with t.span(name, **args):
        yield


# ---------------------------------------------------------------------------
# jax.profiler gating (config-opt-in only)
# ---------------------------------------------------------------------------

def start_jax_profiler(log_dir: str) -> bool:
    """Start a jax.profiler trace into ``log_dir``. Returns False (and
    stays off) when the backend/profiler is unavailable."""
    try:
        os.makedirs(log_dir, exist_ok=True)
        profiler.start_trace(log_dir)
        return True
    except Exception:
        return False


def stop_jax_profiler() -> None:
    try:
        profiler.stop_trace()
    except Exception:
        pass
