"""Reduction of a `jax.profiler` trace to the benchmark's device numbers.

A trace is read into a flat list of `Event`s (plane, line, name, start,
duration). `reduce_trace` then takes:

* the traced window: the host annotation ``perfbench.window`` that the
  runners open around the traced part of a run;
* device busy time: the union of the op intervals on each device
  plane's op line, clipped to the window and averaged over the chips;
* time per op: the clipped durations summed by a stable op name (see
  `stable_name`), so a breakdown and a metric reader find the same op
  after a recompile;
* idle gaps: every stretch of the window in which no op runs, charged
  to the innermost ``perfbench.*`` host annotation that covers its
  midpoint, or to ``outside_spans``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "perfbench.window"
SPAN_PREFIX = "perfbench."
OP_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_NUMBERING = re.compile(r"\.\d+$")
_OUT_TYPE = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def stable_name(name: str) -> str:
    """An op's name without the compiler's numbering. A TPU op event is
    named by its HLO text, ``%copy.41 = f32[1024,2048,50]{...} copy(...)``:
    the instruction name is kept without ``%`` and ``.41``; a generic op
    (one that no jitted function names) gets its output type too, so the
    list table's relayout (``copy f32[1024,2048,50]``) and the catalog's
    (``copy f32[750000,100]``) stay apart."""
    head, sep, rest = name.partition(" = ")
    base = _NUMBERING.sub("", head.strip().lstrip("%"))
    if sep and "jit" not in base and not base.startswith("_"):
        m = _OUT_TYPE.match(rest.strip())
        if m:
            return f"{base} {m.group(1)}"
    return base


def load_xplane(path: str) -> list[Event]:
    """Every event of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a `jax.profiler.trace` directory."""
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


@dataclasses.dataclass
class Reduction:
    """What a trace says about one traced window, in seconds. ``ops``
    and ``idle_gaps`` are averages over the chips."""

    window_s: float
    busy_s: float
    chips: int
    ops: dict[str, float]
    idle_gaps: dict[str, float]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, pattern: str) -> float:
        """Device seconds (per chip) of the ops whose stable name matches
        ``pattern``; 0.0 where none does."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.ops.items() if rx.search(k))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce_trace(events: list[Event]) -> Reduction:
    """Reduce one trace's events (see the module docstring)."""
    windows = [e for e in events if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} annotation")
    w = max(windows, key=lambda e: e.dur_ns)
    w0, w1 = w.start_ns, w.end_ns
    planes = sorted({e.plane for e in events
                     if DEVICE_PLANE.match(e.plane) and e.line == OP_LINE})
    if not planes:
        raise ValueError("the trace has no device op line")
    spans = [e for e in events
             if e.name.startswith(SPAN_PREFIX) and e.name != WINDOW_SPAN
             and not DEVICE_PLANE.match(e.plane)]
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    busy = 0.0
    for plane in planes:
        ivs = []
        for e in events:
            if e.plane != plane or e.line != OP_LINE:
                continue
            a, b = max(e.start_ns, w0), min(e.end_ns, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            key = stable_name(e.name)
            ops[key] = ops.get(key, 0.0) + (b - a) / 1e9
        merged = _union(ivs)
        busy += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                name = _cover(spans, (a + b) / 2)
                gaps[name] = gaps.get(name, 0.0) + (b - a) / 1e9
    n = len(planes)
    return Reduction(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy / n / 1e9,
        chips=n,
        ops={k: v / n for k, v in ops.items()},
        idle_gaps={k: v / n for k, v in gaps.items()},
    )


def _cover(spans: list[Event], t: float) -> str:
    """The innermost (shortest) span covering time ``t``."""
    best = None
    for s in spans:
        if s.start_ns <= t <= s.end_ns and (best is None or s.dur_ns < best.dur_ns):
            best = s
    return best.name[len(SPAN_PREFIX):] if best is not None else "outside_spans"
