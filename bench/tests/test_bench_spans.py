"""The program's own ``oobleck.*`` host spans in a trace reduction, and
the ``step_programs`` reader, on hand-placed events."""
import pytest
from bench.tests.test_bench_rehearsal import CELLS, synthetic_ctx

from bench import harness, trace
from bench.trace import Event, Reduction

DEV, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
MS = 1_000_000


def program_spans():
    """The trainer's spans inside the synthetic context's two steps and
    its failure (bench.recover from 500 to 700 ms, a copy op at 550)."""
    spans = []
    for t0 in (0, 700 * MS):
        spans += [("oobleck.step.grads", t0, 8 * MS),
                  ("oobleck.step.inputs", t0, 3 * MS),
                  ("oobleck.step.sync", t0 + 8 * MS, 6 * MS),
                  ("oobleck.step.update", t0 + 14 * MS, 5 * MS)]
    spans += [("oobleck.recover.replan", 500 * MS, 1 * MS),
              ("oobleck.plan.failure", 500 * MS, 1 * MS // 2),
              ("oobleck.recover.transfer_plan", 501 * MS, 1 * MS),
              ("oobleck.recover.copy", 502 * MS, 138 * MS),
              ("oobleck.recover.bind", 640 * MS, 60 * MS)]
    return [Event(HOST, "python", n, t, d) for n, t, d in spans]


@pytest.mark.parametrize("name", CELLS)
def test_program_spans_change_no_existing_reader(name):
    ctx = synthetic_ctx(name)
    red = ctx["trace"]
    with_spans = dict(ctx, trace=Reduction(red.events + program_spans(),
                                           red.programs))
    for metric in ctx["cell"].per_layer:
        read = harness.load_reader(metric["name"])
        assert read(with_spans) == read(ctx), metric["name"]


def test_program_spans_name_the_idle_gaps():
    ctx = synthetic_ctx(CELLS[0])
    red = ctx["trace"]
    before = dict(red.breakdown()["idle_gaps"])
    after = dict(Reduction(red.events + program_spans(),
                           red.programs).breakdown()["idle_gaps"])
    # the idle stretch from the failure's copy op (ends at 570 ms) to the
    # next step's first op (720 ms) starts inside the copy span, so
    # nothing is left named by bench.recover alone
    assert before["bench.recover"] == pytest.approx(0.15)
    assert "bench.recover" not in after
    assert after["oobleck.recover.copy"] == pytest.approx(0.15)
    # the first step's first 20 ms of idle start under its input staging
    assert after["oobleck.step.inputs"] == pytest.approx(0.02)
    assert sum(after.values()) == pytest.approx(sum(before.values()))
    assert red.open_span(520 * MS) == "bench.recover"


def test_a_recorded_trace_keeps_the_program_spans(tmp_path):
    """``load_events`` on a profiler trace recorded on the CPU keeps the
    benchmark's and the program's host spans, and drops other host
    annotations."""
    import jax
    import jax.numpy as jnp
    from repro.utils.spans import span
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with span("oobleck.step.inputs"):
                with jax.profiler.TraceAnnotation("other.annotation"):
                    jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    names = {e.name for e in trace.load_events(trace.find_xplane(tmp_path))
             if not e.plane.startswith(trace.DEVICE_PLANE)}
    assert names == {"bench.window", "oobleck.step.inputs"}


def steady_ctx(events, steps=2):
    programs = {"grads": [{"module": "jit_grads_fn", "calls": {}}],
                "bscale": [{"module": "jit_bucket_scale", "calls": {}}],
                "bupdate": [{"module": "jit_bucket_update", "calls": {}},
                            {"module": "jit_layer_update", "calls": {}}]}
    events = [Event(HOST, "python", "bench.window", 0, 1000)] + events
    return {"trace": Reduction(events, programs),
            "window": {"steps": steps}}


def module(name, start, plane=DEV):
    return Event(plane, trace.MODULES_LINE, name, start, 10)


def test_step_programs_counts_the_cache_programs_run_per_step():
    read = harness.load_reader("step_programs")
    events = []
    for t0 in (0, 500):
        events += [module("jit_grads_fn(1)", t0),
                   module("jit_bucket_scale(2)", t0 + 20),
                   module("jit_bucket_scale(2)", t0 + 40),
                   module("jit_bucket_update(3)", t0 + 60),
                   module("jit_layer_update(4)", t0 + 80),
                   # an eager op's program is not the trainer's
                   module("jit_add(5)", t0 + 100),
                   # the second chip's copy of a program is not counted
                   module("jit_grads_fn(1)", t0, plane=DEV1)]
    # outside the window
    events.append(module("jit_grads_fn(1)", 2000))
    assert read(steady_ctx(events)) == 5.0
    assert read(steady_ctx(events, steps=0)) is None
    assert read(steady_ctx([module("jit_add(5)", 0)])) is None
    assert read(steady_ctx([])) is None
