"""The trace reduction: busy time, op time, and idle gaps by host span."""

import pathlib
import types

import pytest

from bench import trace
from bench.kernels import paged_attn


def ev(name, start, end, stats=()):
    return types.SimpleNamespace(name=name, start_ns=start, end_ns=end,
                                 duration_ns=end - start, stats=list(stats))


def line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


FUSION = ("%fusion.1 = bf16[4,128]{1,0:T(4,128)(2,1)} fusion(bf16[4,128]{1,0} "
          "%p.1), kind=kLoop, calls=%fused_computation.1")
KERNEL = ("%closed_call.2 = bf16[4,4,32]{2,1,0} custom-call(s32[4,8]{1,0} %a, "
          'bf16[97,16,2,32]{3,2,1,0} %b), custom_call_target="tpu_custom_call"')
COPY = "%copy.3 = bf16[4,97]{1,0:T(2,128)} copy(bf16[4,97]{0,1} %c)"
LOOP = ("%while.6 = (s32[]{:T(128)}, bf16[4,1,128]{2,0,1:T(4,128)(2,1)}) "
        "while((s32[]{:T(128)}, bf16[4,1,128]{2,0,1}) %tuple.7), "
        "condition=%region_5, body=%region_0")


def made_up_trace():
    """A 100 ns window; ops at [10,30) and [20,40) overlap, then [60,70)
    and [90,120) (clipped at 100), the first two inside a loop over [10,40) that
    counts toward busy time but not toward op time. Host: a step span over [0,50), a
    stamp span over [50,80)."""
    host = plane("/host:CPU", [line("python", [
        ev("bench.window", 0, 100), ev("bench.step", 0, 50),
        ev("bench.stamp", 50, 80), ev("PjitFunction(step)", 5, 45)])])
    dev = plane("/device:TPU:0", [
        line("XLA Modules", [ev("jit_step", 10, 70)]),
        line("XLA Ops", [ev(FUSION, 10, 30), ev(KERNEL, 20, 40),
                         ev(FUSION, 60, 70), ev(COPY, 90, 120),
                         ev(LOOP, 10, 40)])])
    return types.SimpleNamespace(planes=[host, dev])


def test_busy_union_op_time_and_idle_by_span():
    s = trace.reduce(made_up_trace())
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((30 + 10 + 10) * 1e-9)
    assert s.op_seconds["%fusion.1 fusion"] == pytest.approx(30e-9)
    assert "%while.6 while" not in s.op_seconds
    assert s.seconds_of(paged_attn.TRACE_NAMES) == pytest.approx(20e-9)
    # gaps [0,10), [40,60) and [70,90), each charged whole to the span
    # open at its midpoint: 5 in the step, 50 in the stamp, 80 in none
    assert s.idle_by_span["bench.step"] == pytest.approx(10e-9)
    assert s.idle_by_span["bench.stamp"] == pytest.approx(20e-9)
    assert s.idle_by_span["(no span)"] == pytest.approx(20e-9)
    assert sum(s.idle_by_span.values()) == pytest.approx(s.window_s - s.busy_s)
    b = s.breakdown()
    assert b["device_ops"][0][0] == "%fusion.1 fusion"
    assert len(b["idle_gaps"]) == 3
    assert "%closed_call.2 custom-call tpu_custom_call" in s.op_seconds


def test_a_trace_without_the_window_span_is_refused():
    t = made_up_trace()
    t.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        trace.reduce(t)


RECORDED = pathlib.Path(__file__).parent / "data" / "engine_steps.xplane.pb"


def test_a_recorded_chip_trace_reduces_to_busy_kernel_and_gaps():
    """Three engine steps of the small granite configuration, traced on
    one TPU v5e by ``python3 -m bench.record_trace tests/benchmark/data``:
    the reduction finds the chip, the Pallas kernel by the name the trace
    gives it, and charges the idle time to the harness's host spans."""
    import jax

    s = trace.reduce(jax.profiler.ProfileData.from_file(str(RECORDED)))
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.seconds_of(paged_attn.TRACE_NAMES) < s.busy_s
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert "bench.step" in s.idle_by_span
