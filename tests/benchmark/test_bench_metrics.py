"""The metric arithmetic, on runs made up here: percentiles, the window's
edges, and a planted stall that every end-to-end metric must feel."""

import dataclasses

import pytest

from bench import flops, harness, spec, stats, trace
from bench.kernels import paged_attn

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def read(name, run):
    return spec.reader(name)(run)


def made_up_run(stall_at=None, stall_s=2.0, n=8, tokens=8, seconds=2.0):
    """``n`` requests due every 0.1 s; each gets its first token 0.3 s after
    it is due and one more every 0.1 s, so most are in flight together.
    With ``stall_at``, every token due at or after that time comes
    ``stall_s`` later: one long gap for each request in flight then."""
    cell = spec.cell("granite-3-2b.chat")
    reqs, step_ends = [], set()
    for i in range(n):
        due = 0.1 * i
        stamps = [due + 0.3 + 0.1 * k for k in range(tokens)]
        if stall_at is not None:
            stamps = [t + stall_s if t >= stall_at else t for t in stamps]
        step_ends.update(stamps)
        reqs.append(harness.ReqRecord(f"r{i}", 10, tokens, due, True,
                                      stamps=stamps))
    ends = sorted(step_ends)
    steps = [harness.StepRecord(a, b, 4, 100, 1)
             for a, b in zip([0.0] + ends, ends)]
    return harness.Run(cell, seconds, 0.0, seconds, 5.0, reqs, steps, PEAKS)


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([5, 1, 4, 2, 3], 90) == 5
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_end_to_end_metrics_of_a_made_up_run():
    run = made_up_run()
    assert read("ttft_p50_ms", run) == pytest.approx(300.0)
    assert read("itl_p95_ms", run) == pytest.approx(100.0)
    in_window = sum(t <= 2.0 for r in run.reqs for t in r.stamps)
    assert read("output_tok_per_s", run) == pytest.approx(in_window / 2.0)
    assert read("setup_s", run) == 5.0


def test_planted_stall_moves_every_end_to_end_metric():
    calm, stalled = made_up_run(), made_up_run(stall_at=0.9)
    assert read("itl_p95_ms", stalled) > read("itl_p95_ms", calm) + 1000
    assert read("ttft_p90_ms", stalled) > read("ttft_p90_ms", calm) + 1000
    assert read("output_tok_per_s", stalled) < read("output_tok_per_s", calm)


def test_trace_readers_give_nothing_without_a_trace():
    run = made_up_run()
    for name in ("paged_attn_roofline.latency", "device_idle_share.latency",
                 "step_mfu.latency"):
        assert read(name, run) is None


def test_trace_readers_from_a_summary():
    run = made_up_run()
    run.traced = (0.2, 1.2)
    steps = run.window_steps(run.traced)
    run.trace = trace.TraceSummary(
        window_s=1.0, busy_s=0.75, n_devices=1,
        op_seconds={"fusion.1": 1.0, "%closed_call.3 custom-call tpu_custom_call": 0.25},
        idle_by_span={"bench.step": 0.5})
    assert read("device_idle_share.latency", run) == pytest.approx(25.0)
    cfg = run.cfg
    f = sum(paged_attn.cost(cfg, s.fed, s.attended)[0] for s in steps)
    b = sum(paged_attn.cost(cfg, s.fed, s.attended)[1] for s in steps)
    want = 100 * max(f / PEAKS["bf16_flops_per_s"],
                     b / PEAKS["hbm_bytes_per_s"]) / 0.25
    assert read("paged_attn_roofline.latency", run) == pytest.approx(want)
    seconds = steps[-1].end - steps[0].start
    mfu = 100 * sum(flops.tokens_flops(cfg, s.fed, s.attended)
                    for s in steps) / (seconds * PEAKS["bf16_flops_per_s"])
    assert read("step_mfu.latency", run) == pytest.approx(mfu)
    # a trace with device ops but no kernel is an error, never 0 or silence
    run.trace = dataclasses.replace(run.trace, op_seconds={"fusion.1": 1.0})
    with pytest.raises(LookupError, match="tpu_custom_call"):
        read("paged_attn_roofline.latency", run)


def test_engine_readers():
    run = made_up_run()
    assert read("step_ms.latency", run) == pytest.approx(100.0, rel=0.5)
    assert read("batch_occupancy.batch", run) == pytest.approx(1.0)
    assert read("queue_wait_ms.p90", run) is None     # no engine requests
