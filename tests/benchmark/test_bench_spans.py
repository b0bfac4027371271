"""The readers of the program's step log (``bench/spans.py``):
``host_gap_ms``, ``device_wait_ms`` and ``prefill_tok_per_step``, on logs
made up here, on a log that lost the window's start, and on a whole run
of the small cell."""

import collections
import sys

import pytest

from bench import harness, spec
from smallcell import run, small_cell

sys.path.insert(0, str(spec.ROOT / "src"))
from repro.serve import tracing  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ("host_gap_ms.latency", "device_wait_ms.latency",
           "prefill_tok_per_step.latency")


def read(name, r):
    return spec.reader(name)(r)


def made_up(monkeypatch, bound=64):
    """Four 100 ms steps from 1.0 s in a window [0.95, 1.45]: each launch
    returns 10 ms in, its wait ends 90 ms in after 80 ms of waiting, and
    step i feeds i prompt tokens. A warm-up step before the window and a
    step after it are logged too. The harness saw the same four steps."""
    monkeypatch.setattr(tracing, "_LOG", collections.deque(maxlen=bound))
    starts = [0.5] + [1.0 + 0.1 * i for i in range(4)] + [1.5]
    for i, s in enumerate(starts):
        tracing.record(tracing.StepTrace(
            "engine", s, s + 0.095, launched=s + 0.01, waited=s + 0.09,
            phases={"engine.wait": 0.08, "engine.launch": 0.005},
            prompt_fed=i - 1))
    steps = [harness.StepRecord(s - 1e-4, s + 0.0951, 8, 100, 4)
             for s in starts[1:5]]
    return harness.Run(spec.cell("granite-3-2b.chat"), 0.5, 0.95, 1.45,
                       5.0, [], steps, PEAKS)


def test_readers_of_a_made_up_log(monkeypatch):
    r = made_up(monkeypatch)
    assert read("host_gap_ms.latency", r) == pytest.approx(20.0)
    assert read("host_gap_ms.batch", r) == pytest.approx(20.0)
    assert read("device_wait_ms.latency", r) == pytest.approx(80.0)
    assert read("device_wait_ms.batch", r) == pytest.approx(80.0)
    assert read("prefill_tok_per_step.latency", r) == pytest.approx(1.5)


@pytest.mark.parametrize("why", ["empty log", "program without a log"])
def test_readers_give_nothing_without_program_records(monkeypatch, why):
    r = made_up(monkeypatch)
    if why == "empty log":
        tracing._LOG.clear()
    else:
        import repro.serve

        monkeypatch.delattr(repro.serve, "tracing")
        monkeypatch.setitem(sys.modules, "repro.serve.tracing", None)
    for name in READERS:
        assert read(name, r) is None


def test_readers_refuse_a_log_that_lost_the_window_start(monkeypatch):
    r = made_up(monkeypatch, bound=3)
    for name in READERS:
        with pytest.raises(LookupError, match="dropped"):
            read(name, r)


def test_readers_refuse_a_window_of_two_engines(monkeypatch):
    r = made_up(monkeypatch)
    tracing.record(tracing.StepTrace("other", 1.2, 1.25))
    with pytest.raises(ValueError, match="several engines"):
        read("host_gap_ms.latency", r)


@pytest.mark.parametrize("loop", ["open", "backlog"])
def test_readers_of_a_whole_small_run(loop):
    """On a CPU run of the small cell every reader reports, and the host
    gap and the device wait of a step add up to no more than the
    harness's step time around ``engine.step()``, with room for the
    harness's own work between two steps."""
    r, out = run(small_cell(f"spans-{loop}", loop, 0.03))
    assert out["correct"], out["checks"]
    variant = "latency" if loop == "open" else "batch"
    gap = read(f"host_gap_ms.{variant}", r)
    wait = read(f"device_wait_ms.{variant}", r)
    step = read(f"step_ms.{variant}", r)
    assert gap > 0 and wait > 0
    assert gap + wait <= step * 1.1 + 2.0, (gap, wait, step)
    prefill = read("prefill_tok_per_step.latency", r)
    fed = sum(s.fed - s.emitted for s in r.window_steps())
    assert 0 < prefill <= fed / len(r.window_steps()) + 1, prefill
