"""The trace reduction: busy and idle time, time per op, and idle gaps
charged to the harness's host annotations."""
import pytest

from perfbench.harness import tracing
from perfbench.harness.tracing import Event, reduce_trace

DEV, OPS, HOST = "/device:TPU:0", tracing.OP_LINE, "/host:CPU"


def _events(extra_plane=False):
    ev = [
        Event(HOST, "python", "perfbench.window", 50, 600),          # [50, 650]
        Event(HOST, "python", "perfbench.wait", 200, 220),           # [200, 420]
        Event(HOST, "python", "perfbench.serve_batch", 420, 220),    # [420, 640]
        Event(HOST, "python", "PjitFunction(query)", 430, 5),
        Event(DEV, OPS, "fusion.1", 100, 100),                       # [100, 200]
        Event(DEV, OPS, "fusion.2", 150, 100),                       # [150, 250]
        Event(DEV, OPS, "%copy.41 = f32[1024,2048,50]{2,1,0} copy(f32[1024,2048,50]{1,0,2} %x)",
              400, 100),                                             # [400, 500]
        Event(DEV, OPS, "_ivf_topk_impl.2", 600, 100),               # [600, 700] clipped
        Event(DEV, "XLA Modules", "jit_query", 100, 600),            # not an op line
        Event(DEV, OPS, "fusion.3", 0, 20),                          # before the window
    ]
    if extra_plane:
        ev += [Event("/device:TPU:1", OPS, "%copy.7 = f32[1024,2048,50]{2,1,0} copy(%y)",
                     100, 500)]                                       # [100, 600]
    return ev


def test_window_busy_idle_one_chip():
    r = reduce_trace(_events())
    assert r.window_s == pytest.approx(600e-9)
    # union [100, 250] + [400, 500] + [600, 650]
    assert r.busy_s == pytest.approx(300e-9)
    assert r.idle_share == pytest.approx(0.5)
    assert r.chips == 1


def test_op_sums_use_stable_names_and_clip_to_the_window():
    r = reduce_trace(_events())
    assert r.ops == pytest.approx({"fusion": 200e-9, "copy f32[1024,2048,50]": 100e-9,
                                   "_ivf_topk_impl": 50e-9})
    assert r.op_seconds(r"ivf_topk") == pytest.approx(50e-9)
    assert r.op_seconds(r"^copy ") == pytest.approx(100e-9)
    assert r.op_seconds(r"^nothing$") == 0.0


def test_idle_gaps_go_to_the_innermost_covering_span():
    r = reduce_trace(_events())
    # [50, 100] lies in no span, [250, 400] in wait, [500, 600] in serve_batch
    assert r.idle_gaps == pytest.approx({"outside_spans": 50e-9, "wait": 150e-9,
                                         "serve_batch": 100e-9})
    b = r.breakdown()
    assert b["idle_gaps"][0][0] == "wait"
    assert [k for k, _ in b["device_ops"]] == ["fusion", "copy f32[1024,2048,50]",
                                               "_ivf_topk_impl"]


def test_two_chips_average():
    r = reduce_trace(_events(extra_plane=True))
    assert r.chips == 2
    assert r.busy_s == pytest.approx((300e-9 + 500e-9) / 2)
    assert r.ops["copy f32[1024,2048,50]"] == pytest.approx((100e-9 + 500e-9) / 2)


@pytest.mark.parametrize("drop", ["perfbench.window", OPS])
def test_a_trace_without_window_or_ops_is_an_error(drop):
    ev = [e for e in _events() if e.name != drop and e.line != drop]
    with pytest.raises(ValueError):
        reduce_trace(ev)


@pytest.mark.parametrize("name,want", [
    ("%copy.41 = f32[1024,2048,50]{2,1,0:T(8,128)} copy(f32[1024,2048,50]{1,0,2} %s.1)",
     "copy f32[1024,2048,50]"),
    ("%jvp_jit__ivf_topk_impl__.1 = (f32[32,1,256]{2,1,0}, s32[32,1,256]) custom-call(%a)",
     "jvp_jit__ivf_topk_impl__"),
    ("%_ivf_topk_impl.2 = (f32[8,1,128]{2,1,0}, s32[8,1,128]) custom-call(%a)",
     "_ivf_topk_impl"),
    ("%custom-call = (f32[8,8]{1,0}, s32[8,8]{1,0}) custom-call(f32[8,1024] %f)",
     "custom-call f32[8,8]"),
    ("copy.41", "copy"), ("fusion", "fusion")])
def test_stable_name(name, want):
    assert tracing.stable_name(name) == want


def _fixture(name):
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "fixtures", f"v5e_{name}_trace.json")
    with open(path) as f:
        return [Event(*e) for e in json.load(f)["events"]]


def _brute_busy(events, w0, w1, step=100.0):
    """Busy time by sampling the window every ``step`` ns."""
    import numpy as np

    t = np.arange(w0, w1, step) + step / 2
    busy = np.zeros(t.shape, bool)
    for e in events:
        if tracing.DEVICE_PLANE.match(e.plane) and e.line == OPS:
            busy |= (t >= e.start_ns) & (t < e.end_ns)
    return busy.sum() * step, t, busy


@pytest.mark.parametrize("name", ["train", "serve"])
def test_recorded_v5e_trace(name):
    """A cut of a real trace of the harness's window on one v5e."""
    ev = _fixture(name)
    r = reduce_trace(ev)
    win = next(e for e in ev if e.name == tracing.WINDOW_SPAN)
    busy_ns, t, busy = _brute_busy(ev, win.start_ns, win.end_ns)
    assert r.window_s == pytest.approx(win.dur_ns / 1e9)
    assert r.busy_s == pytest.approx(busy_ns / 1e9, rel=2e-3)
    assert 0.0 < r.idle_share < 1.0
    # every op second is inside the window, and the gaps fill the rest
    assert sum(r.ops.values()) >= r.busy_s * (1 - 1e-9)
    assert sum(r.idle_gaps.values()) == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    # the idle time the host spans cover, by sampling
    spans = [e for e in ev if e.name.startswith("perfbench.") and e.name != tracing.WINDOW_SPAN]
    covered = sum(((t >= s.start_ns) & (t < s.end_ns) & ~busy).sum() for s in spans) * 100.0
    charged = sum(v for k, v in r.idle_gaps.items() if k != "outside_spans")
    assert charged == pytest.approx(covered / 1e9, rel=0.05, abs=2e-6)
    kernel = r.op_seconds(r"ivf_topk")
    assert 0.0 < kernel < r.busy_s


def test_recorded_serve_trace_names_the_list_table_relayout():
    r = reduce_trace(_fixture("serve"))
    assert "copy f32[1024,2048,50]" in r.ops
    assert "_ivf_topk_impl" in r.ops
    top = r.breakdown()["device_ops"][0][0]
    assert top in ("_ivf_topk_impl", "copy f32[1024,2048,50]")
