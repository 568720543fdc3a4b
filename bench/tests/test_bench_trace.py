"""The trace reduction (bench/trace.py) on hand-placed events laid out
as a TPU v5e trace is: device ops and program executions on the
``/device:TPU:0`` plane, the benchmark's spans on the host's."""

import pytest

from bench import trace
from bench.trace import Event, Reduction

DEV = "/device:TPU:0"
HOST = "/host:CPU"

# the form of a compiled TPU program's Pallas calls (v5e compile)
HLO = """HloModule jit_grads_fn, is_scheduled=true, entry_computation_layout={}

%body {
  %jvp_jit_flash_attention_fwd__.1 = (f32[1,2,256,64]{3,2,1,0:T(8,128)S(1)}, f32[1,2,1,256]{3,2,1,0:T(1,128)S(1)}) custom-call(%bitcast.5, %bitcast.6, %bitcast.7), custom_call_target="tpu_custom_call", operand_layout_constraints={}, backend_config={"custom_call_config":{"body":"TUzvUgFNTElS"}}
  %transpose_jvp_jit_ssd_bwd___.3 = f32[8] custom-call(%p), custom_call_target="tpu_custom_call", backend_config={}
  %custom-call.67 = f32[8] custom-call(), custom_call_target="AllocateBuffer"
  ROOT %fusion.1 = f32[8] fusion(%p), kind=kLoop, calls=%f
}
"""


def ev(line, name, start, dur, plane=DEV):
    return Event(plane, line, name, start, dur)


FWD = "%jvp_jit_flash_attention_fwd__.1 = (f32[1,2,256,64]) custom-call()"
BWD = "%transpose_jvp_jit_ssd_bwd___.3 = f32[8] custom-call(%p)"


def hand_trace():
    return [
        ev("python", "bench.window", 0, 1000, plane=HOST),
        ev("python", "bench.step", 0, 300, plane=HOST),
        ev("python", "bench.loss", 300, 500, plane=HOST),
        ev("python", "bench.batch", 800, 200, plane=HOST),
        ev(trace.MODULES_LINE, "jit_grads_fn(7)", 100, 400),
        ev(trace.MODULES_LINE, "jit_upd(9)", 600, 100),
        # overlapping ops: 100-300 and 200-400 cover 300 ns
        ev(trace.OPS_LINE, FWD, 100, 200),
        ev(trace.OPS_LINE, BWD, 200, 200),
        ev(trace.OPS_LINE, "%fusion.1 = f32[8] fusion(%p)", 450, 50),
        # an op of another program with a clashing instruction name
        ev(trace.OPS_LINE, FWD, 600, 100),
        # partly outside the window: only 950-1000 counts
        ev(trace.OPS_LINE, "%copy.1 = f32[8] copy(%p)", 950, 100),
    ]


PROGRAMS = {"grads": [{"module": "jit_grads_fn",
                        "calls": trace.kernel_calls(HLO)}],
            "bupdate": [{"module": "jit_upd", "calls": {}}]}


def test_busy_union_of_overlapping_events():
    red = Reduction(hand_trace(), PROGRAMS)
    # 100-400, 450-500, 600-700, 950-1000
    assert red.busy_ns() == 300 + 50 + 100 + 50
    assert red.window_s == pytest.approx(1e-6)
    assert red.idle_share() == pytest.approx(1 - 500 / 1000)
    assert red.busy_ns(150, 250) == 100
    assert trace.covered([(0, 10), (5, 20), (30, 40)]) == 30


def test_module_time_by_program_kind():
    red = Reduction(hand_trace(), PROGRAMS)
    assert red.program_ns(["grads"]) == 400
    assert red.program_ns(["bupdate", "bpack"]) == 100
    assert red.program_ns(["serve"]) == 0


def test_custom_calls_attributed_to_kernels_from_the_hlo():
    assert trace.kernel_calls(HLO) == {
        "jvp_jit_flash_attention_fwd__.1": "flash_attention_fwd",
        "transpose_jvp_jit_ssd_bwd___.3": "ssd_bwd"}
    red = Reduction(hand_trace(), PROGRAMS)
    flash = {"flash_attention", "flash_attention_fwd", "flash_attention_bwd"}
    # the clashing instruction name inside jit_upd is not counted
    assert red.kernel_ns(flash) == 200
    assert red.kernel_ns({"ssd_fwd", "ssd_bwd"}) == 200
    assert red.kernel_ns({"fused_add_rmsnorm"}) == 0


def test_self_time_leaves_out_nested_ops():
    events = [ev("python", "bench.window", 0, 100, plane=HOST),
              ev(trace.MODULES_LINE, "jit_grads_fn(1)", 0, 100),
              ev(trace.OPS_LINE, "%while.1 = (f32[8]) while(%p)", 0, 100),
              ev(trace.OPS_LINE, "%fusion.2 = f32[8] fusion(%p)", 10, 30),
              ev(trace.OPS_LINE, "%fusion.2 = f32[8] fusion(%p)", 50, 30)]
    red = Reduction(events, PROGRAMS)
    assert red.self_times() == {"jit_grads_fn/while.1": 40,
                                "jit_grads_fn/fusion.2": 60}


def test_idle_gaps_keyed_by_the_open_host_span():
    red = Reduction(hand_trace(), PROGRAMS)
    gaps = red.idle_gaps()
    # 0-100 under bench.step; 400-450, 500-600 and 700-950 start under
    # bench.loss (the last runs on into bench.batch)
    assert gaps == [("bench.loss", 250), ("bench.step", 100),
                    ("bench.loss", 100), ("bench.loss", 50)]
    assert red.open_span(850) == "bench.batch"
    bd = red.breakdown()
    assert bd["idle_gaps"] == [["bench.loss", 400e-9], ["bench.step", 100e-9]]
    # the ssd op (200-400) sits partly inside the flash op (100-300),
    # which keeps 100 ns of its own
    assert bd["device_ops"][0] == [
        "jit_grads_fn/transpose_jvp_jit_ssd_bwd___.3", 200e-9]
    assert sorted(bd["device_ops"][1:3]) == [
        ["jit_grads_fn/jvp_jit_flash_attention_fwd__.1", 100e-9],
        ["jit_upd/jvp_jit_flash_attention_fwd__.1", 100e-9]]


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        Reduction([e for e in hand_trace() if e.name != "bench.window"])

