"""Fault-tolerance controller: heartbeats, stragglers, rescale, backoff."""

import pytest

from hypothesis import given, settings, strategies as st

from repro.runtime.ft import FTConfig, FTController, WorkerState


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def make(n=8, **kw):
    clock = FakeClock()
    ctl = FTController(n, FTConfig(**kw), clock=clock)
    return ctl, clock


def test_heartbeat_timeout_marks_dead():
    ctl, clock = make(4, heartbeat_timeout_s=10)
    clock.advance(5)
    for i in range(4):
        ctl.report_heartbeat(i)
    clock.advance(11)
    ctl.report_heartbeat(0)
    ctl.report_heartbeat(1)
    res = ctl.tick()
    assert sorted(res["dead"]) == [2, 3]
    assert ctl.healthy_workers() == [0, 1]


def test_dead_worker_can_rejoin():
    ctl, clock = make(2, heartbeat_timeout_s=1)
    clock.advance(2)
    ctl.tick()
    assert ctl.workers[0].state is WorkerState.DEAD
    ctl.report_heartbeat(0)
    assert ctl.workers[0].state is WorkerState.HEALTHY


def test_straggler_detection_needs_streak():
    ctl, clock = make(4, straggler_factor=1.5, straggler_streak=3)
    for step in range(4):
        for i in range(4):
            ctl.report_heartbeat(i)
            ctl.report_step_time(i, 1.0 if i else 2.5)  # worker 0 slow
        res = ctl.tick()
    assert 0 in res["stragglers"]
    assert ctl.workers[0].state is WorkerState.STRAGGLING
    # recovery clears the flag
    ctl.report_step_time(0, 1.0)
    for i in range(1, 4):
        ctl.report_step_time(i, 1.0)
    ctl.tick()
    assert ctl.workers[0].state is WorkerState.HEALTHY


def test_rescale_plan_shrinks_to_power_of_two():
    ctl, clock = make(512, heartbeat_timeout_s=1)
    # kill one pod's worth: 300 remain
    for i in range(300):
        ctl.report_heartbeat(i)
    clock.advance(2)
    for i in range(300):
        ctl.report_heartbeat(i)
    ctl.tick()
    plan = ctl.rescale_plan((2, 16, 16), axis=0)
    assert plan == (1, 16, 16)  # 256 <= 300 healthy


def test_rescale_none_when_full():
    ctl, _ = make(512)
    assert ctl.rescale_plan((2, 16, 16)) is None


@settings(max_examples=100, deadline=None)
@given(n_workers=st.integers(1, 32), n_mb=st.integers(1, 256),
       slow=st.lists(st.integers(0, 31), max_size=8))
def test_microbatch_shares_conserve_work(n_workers, n_mb, slow):
    ctl, _ = make(n_workers)
    for s in slow:
        if s < n_workers:
            ctl.workers[s].state = WorkerState.STRAGGLING
    shares = ctl.microbatch_shares(n_mb)
    assert sum(shares.values()) == n_mb          # nothing dropped
    if any(s < n_workers for s in slow) and n_workers > 1:
        healthy = [shares[i] for i, w in ctl.workers.items()
                   if w.state is WorkerState.HEALTHY]
        straggling = [shares[i] for i, w in ctl.workers.items()
                      if w.state is WorkerState.STRAGGLING]
        if healthy and straggling and n_mb >= n_workers * 2:
            assert max(straggling) <= max(healthy)  # stragglers never loaded more


def test_restart_backoff_doubles_then_exhausts():
    ctl, _ = make(1, max_restarts=3, backoff_base_s=2.0)
    assert ctl.restart_delay() == 2.0
    assert ctl.restart_delay() == 4.0
    assert ctl.restart_delay() == 8.0
    assert ctl.restart_delay() is None


def test_add_worker_and_report_failure():
    ctl, clock = make(0)
    a, b = ctl.add_worker(), ctl.add_worker()
    assert (a, b) == (0, 1)
    ctl.report_failure(a, reason="engine crash")
    assert ctl.workers[a].state is WorkerState.DEAD
    assert ctl.healthy_workers() == [b]
    assert any("engine crash" in msg for _, msg in ctl.events)
    # a second failure report is idempotent (one event, not two)
    ctl.report_failure(a, reason="engine crash")
    assert sum("declared dead" in m for _, m in ctl.events) == 1
    # heartbeat rejoins, exactly like a timeout death
    ctl.report_heartbeat(a)
    assert ctl.workers[a].state is WorkerState.HEALTHY
    assert sorted(ctl.healthy_workers()) == [a, b]


def test_add_worker_ids_continue_after_static_init():
    ctl, _ = make(3)
    assert ctl.add_worker() == 3
    assert ctl.add_worker() == 4


# ---------------------------------------------------------------------------
# RequestJournal retention horizon
# ---------------------------------------------------------------------------


def _filled_journal(n_done=5, n_inflight=2, horizon=None):
    from repro.runtime.ft import RequestJournal

    j = RequestJournal(horizon=horizon)
    for i in range(n_done):
        j.open(f"d{i}", [1, 2, 3], 4)
        j.record_token(f"d{i}", 10 + i)
        j.complete(f"d{i}")
    for i in range(n_inflight):
        j.open(f"f{i}", [1, 2, 3], 4)
    return j


def test_journal_horizon_evicts_oldest_completed_only():
    j = _filled_journal(n_done=5, n_inflight=2, horizon=2)
    s = j.size()
    # only the 2 newest completed records survive; in-flight all survive
    assert s["records"] == 2 + 2 and s["in_flight"] == 2
    assert s["auto_evicted"] == 3 and s["horizon"] == 2
    assert not j.has("d0") and not j.has("d1") and not j.has("d2")
    assert j.has("d3") and j.has("d4")
    assert [r.request_id for r in j.incomplete()] == ["f0", "f1"]


def test_journal_unbounded_without_horizon():
    j = _filled_journal(n_done=5, n_inflight=2, horizon=None)
    s = j.size()
    assert s["records"] == 7 and s["auto_evicted"] == 0
    assert s["tokens"] == 7 * 3 + 5        # prompts + one token per done
    assert s["approx_bytes"] == 400 * 7 + 28 * s["tokens"]


def test_journal_evict_forgiving_after_horizon():
    j = _filled_journal(n_done=3, n_inflight=1, horizon=1)
    j.evict("d0")                          # horizon got there first: no-op
    j.evict("d2")                          # still retained: explicit drop
    assert not j.has("d2")
    with pytest.raises(ValueError, match="in flight"):
        j.evict("f0")                      # never evict replay state
    assert j.has("f0")


def test_journal_horizon_validation():
    from repro.runtime.ft import RequestJournal

    with pytest.raises(ValueError, match="horizon"):
        RequestJournal(horizon=-1)


def test_cluster_journal_propagates_horizon():
    from repro.runtime.ft import ClusterJournal

    cj = ClusterJournal(horizon=1)
    for eng in ("a", "b"):
        j = cj.journal(eng)
        assert j.horizon == 1
        for i in range(3):
            j.open(f"{eng}{i}", [1], 1)
            j.complete(f"{eng}{i}")
    assert cj.journal("a").size()["records"] == 1
    assert cj.journal("b").size()["auto_evicted"] == 2
