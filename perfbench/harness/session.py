"""What every runner shares: the window's clock discipline, the traced
window, device memory, and the comparison's bookkeeping."""
from __future__ import annotations

import contextlib
import gc
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from perfbench.harness import tracing

clock = time.perf_counter


def log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


@contextlib.contextmanager
def quiet_gc():
    """The measured window runs with the collector frozen and off, so no
    collection pause lands inside it."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()
        gc.unfreeze()


def span(name: str):
    """A host annotation in the profiler's trace (``perfbench.<name>``)."""
    import jax

    return jax.profiler.TraceAnnotation(tracing.SPAN_PREFIX + name)


@contextlib.contextmanager
def traced_window():
    """Trace what runs inside into a temporary directory under TMPDIR,
    inside a ``perfbench.window`` annotation; yields a dict that holds
    the `tracing.Reduction` under "reduction" once the block ends. The
    raw trace is deleted after it is read."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    out: dict = {}
    tmp = tempfile.mkdtemp(prefix="perfbench_trace_")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
                yield out
        finally:
            jax.profiler.stop_trace()
        out["reduction"] = tracing.reduce_trace(
            tracing.load_xplane(tracing.find_xplane(tmp)))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest device, where the backend says."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def finite(x: float) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


class Checks:
    """Numbers compared with the reference, each with its limit. A number
    passes when it is finite and at most its limit."""

    def __init__(self):
        self.items: dict[str, dict] = {}

    def add(self, name: str, value: float, limit: float) -> None:
        self.items[name] = {"value": float(value), "limit": float(limit)}

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(
            finite(c["value"]) and c["value"] <= c["limit"]
            for c in self.items.values())


def index_faults(lists: np.ndarray, num_items: int) -> int:
    """How far an IVF partition is from holding every catalog item once:
    items no list holds, plus repeated entries, plus ids out of range."""
    ids = lists[lists >= 0]
    held = np.unique(ids)
    bad = int(np.sum(held >= num_items))
    return int(num_items - (held.size - bad)) + int(ids.size - held.size) + bad


class CompileWatch:
    """Counts programs traced or compiled while ``active`` — the window
    should see none (everything is warmed up in set-up)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring

        self.active = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, _secs, **_kw):
        if self.active and name in self.EVENTS:
            self.count += 1

    @contextlib.contextmanager
    def watching(self):
        self.active = True
        try:
            yield self
        finally:
            self.active = False
