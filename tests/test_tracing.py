"""The engine's step log and host spans (``repro.serve.tracing``): one
record per working step whose counters agree with the engine's own, a
bounded log, and spans that land in a profiler trace nested in
``engine.step``."""

import collections
import pathlib

import jax
import pytest

from engine_sim import make_engine, make_requests
from repro.serve import tracing

PHASES = {"engine.meter", "engine.admit", "engine.batch", "engine.tables",
          "engine.launch", "engine.account", "engine.wait", "engine.retire",
          "engine.recover"}


@pytest.mark.parametrize("async_dispatch", [False, True])
def test_one_record_per_working_step_with_the_engines_counts(async_dispatch):
    eng, _ = make_engine("granite_3_2b", slots=3, name="traced",
                         async_dispatch=async_dispatch)
    for r in make_requests(4, prompt_len=5, new_tokens=4):
        eng.submit(r)
    records = []
    while True:
        gen, fed = eng.tokens_generated, eng.prompt_tokens_processed
        n = len(tracing.steps("traced"))
        worked = eng.step()
        new = tracing.steps("traced")[n:]
        if not worked:
            assert new == []                   # an idle step logs nothing
            break
        assert len(new) == 1
        t = new[0]
        assert t.emitted == eng.tokens_generated - gen
        assert t.prompt_fed == eng.prompt_tokens_processed - fed
        assert t.queued == len(eng.queue)
        assert set(t.phases) <= PHASES
        assert t.start <= t.end
        assert sum(t.phases.values()) <= t.end - t.start
        marks = [x for x in (t.launched, t.waited) if x is not None]
        assert marks == sorted(marks)
        assert all(t.start <= x <= t.end for x in marks)
        records.append(t)
    assert sum(t.admitted for t in records) == 4
    assert records[0].admitted == 3 and records[0].queued == 1
    assert sum(t.emitted for t in records) == 4 * 4
    assert sum(t.prompt_fed + t.decode_fed for t in records) == (
        eng.prompt_tokens_processed + eng.tokens_generated - 4)
    assert tracing.steps("traced", since=records[1].start) == records[1:]
    assert all(t.launched is not None for t in records[:-1])


def test_the_log_is_bounded_and_drops_the_oldest(monkeypatch):
    assert tracing._LOG.maxlen == tracing.LOG_BOUND
    monkeypatch.setattr(tracing, "_LOG", collections.deque(maxlen=3))
    made = [tracing.StepTrace("e", float(i), float(i) + 0.5)
            for i in range(5)]
    for t in made:
        tracing.record(t)
    assert tracing.steps() == made[2:]
    assert tracing.steps("e", since=3.0) == made[3:]
    assert tracing.steps("other") == []


def test_spans_land_in_a_profiler_trace_nested_in_the_step(tmp_path):
    eng, _ = make_engine("granite_3_2b", slots=3)
    for r in make_requests(3):
        eng.submit(r)
    eng.step()                                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    path = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("engine.")]
    steps = [e for e in events if e[0] == "engine.step"]
    phases = [e for e in events if e[0] != "engine.step"]
    assert len(steps) == 3
    assert {"engine.launch", "engine.wait", "engine.tables"} <= {
        e[0] for e in phases}
    assert {e[0] for e in phases} <= PHASES
    for name, a, b in phases:
        assert any(s <= a and b <= e for _, s, e in steps), name
