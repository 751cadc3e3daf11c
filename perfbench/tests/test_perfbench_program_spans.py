"""The program's own spans (`repro.obs.trace`) as the harness meets
them: inside a profiler session they land in the trace that
`harness/tracing.py` reads, on the host plane, nested in the harness's
window and carrying their ids; an untraced run installs no tracer, so
the program's spans stay no-ops wherever an end-to-end number is
taken."""
import pytest

from perfbench.harness import tracing

TRAIN = "train.fopo-paper.paper"
SERVE = "serve.sasrec.saturated"


def test_program_spans_land_in_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    from repro.obs.trace import Tracer, span, tracing as program_tracing

    f = jax.jit(lambda x: x @ x)
    x = jax.numpy.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with program_tracing(Tracer()), \
                jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            for step in range(2):
                with span("train_step", step=step):
                    with span("dispatch", step=step):
                        y = f(x)
                    with span("drain", step=step):
                        y.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = tracing.find_xplane(str(tmp_path))
    events = tracing.load_xplane(path)
    (window,) = [e for e in events if e.name == tracing.WINDOW_SPAN]
    program = [e for e in events if e.name.startswith("repro.")]
    assert sorted(e.name for e in program) == sorted(
        ["repro.train_step", "repro.dispatch", "repro.drain"] * 2)
    for e in program:
        assert e.plane == window.plane == "/host:CPU"
        assert window.start_ns <= e.start_ns and e.end_ns <= window.end_ns
    steps = sorted((e for e in program if e.name == "repro.train_step"),
                   key=lambda e: e.start_ns)
    for s in steps:
        inner = [e for e in program if e is not s
                 and s.start_ns <= e.start_ns and e.end_ns <= s.end_ns]
        assert sorted(e.name for e in inner) == ["repro.dispatch", "repro.drain"]
    stats = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "repro.train_step":
                    stats[e.start_ns] = dict(e.stats)
    assert [stats[s.start_ns] for s in steps] == [{"step": 0}, {"step": 1}]


def _train_watch(seen):
    from repro.obs import trace

    def tamper(trainer):
        seen.append(trace.current())
        train = trainer.train

        def watched(*a, **kw):
            seen.append(trace.current())
            return train(*a, **kw)

        trainer.train = watched

    return tamper


def _serve_watch(seen):
    from repro.obs import trace

    def tamper(route, engine):
        seen.append(trace.current())
        serve = engine.serve_batch

        def watched(*a, **kw):
            seen.append(trace.current())
            return serve(*a, **kw)

        engine.serve_batch = watched

    return tamper


@pytest.mark.parametrize("workload,watch", [(TRAIN, _train_watch), (SERVE, _serve_watch)],
                         ids=["train", "serve"])
def test_an_untraced_run_installs_no_program_tracer(small_run, workload, watch):
    seen = []
    rc, res = small_run(workload, tamper=watch(seen))
    assert rc == 0 and res["correct"] is True, res
    assert len(seen) > 2  # set-up and the calls in the window
    assert all(t is None for t in seen)
