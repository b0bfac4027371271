"""Energy-metered serving: joule attribution, conservation, and policies.

The tentpole invariants the meter is held to, each driven through the
real engine/cluster stack:

* **Conservation** — over any trace, the platform energy integral splits
  exactly: ``total_uj == attributed_uj + overhead_uj`` and
  ``attributed_uj == Σ Request.energy_uj`` over every submitted request
  (property-tested over randomised op sequences with hypothesis).
* **Observability only** — metering, DVFS points, and idle-bank gating
  change *when* energy is charged, never *what* the engine computes:
  completed tokens are bit-identical to an unmetered run across the
  paged/lanes/async/windowed backends.
* **Attribution is physical** — non-negative, monotone per step,
  shared-prefix holding costs split ``1/refcount``, replay energy after
  a preemption or crash is charged on top (like latency), and
  accumulated joules survive a crash rebuild.
* **Policies act on the meter** — the DVFS throttle admits by dropping
  the operating point instead of stalling; energy-aware admission sheds
  heads whose projected joules/token busts their cap.
"""

import dataclasses
import pathlib

import pytest

from engine_sim import (CANONICAL, ClusterSimulator, FakeClock, PowerBudget,
                        Request, Simulator, add_smoke_engine, burst_trace,
                        make_cluster, make_engine, make_requests,
                        shared_prefix_reqs, smoke_params, standalone_tokens,
                        staggered_trace, tag_engine, tokens_of)
from repro.core import energy
from repro.runtime.ft import FTConfig
from repro.serve.cluster import SchedPolicy
from repro.serve.energy_meter import EnergyMeter
from repro.serve.engine import ContinuousBatchingEngine
from repro.serve.loadgen import TenantSpec
from repro.serve.metrics import SLO, ServeMetrics
from repro.serve.sampling import SamplingParams

from hypothesis import given, settings, strategies as st

TESTS = str(pathlib.Path(__file__).resolve().parent)

SAMPLED = SamplingParams(temperature=0.9, top_k=20, top_p=0.9, seed=5)

# engine.stats() keys that are lifetime counters (monotone by contract);
# gauges like `queued`/`active`/`journal` are deliberately absent
COUNTER_KEYS = ("steps", "tokens_generated", "prompt_tokens_processed",
                "prompt_tokens_reused", "pages_recycled", "stalls",
                "admission_stalls", "rematches", "rematched_tokens",
                "completed", "sampled_requests", "rejected", "shed",
                "token_faults", "replays")
ENERGY_COUNTER_KEYS = ("total_uj", "attributed_uj", "overhead_uj",
                       "prefill_uj", "decode_uj", "pages_uj", "retention_uj",
                       "host_uj", "idle_uj", "dvfs_switches")


def _counters(eng) -> list:
    stats = eng.stats()
    vals = [stats[k] for k in COUNTER_KEYS]
    if "energy" in stats:
        vals += [stats["energy"][k] for k in ENERGY_COUNTER_KEYS]
    return vals


def _assert_conserved(eng, requests) -> None:
    """The meter's double-entry bookkeeping balances exactly."""
    stats = eng.stats()["energy"]
    assert stats["total_uj"] == pytest.approx(
        stats["attributed_uj"] + stats["overhead_uj"], rel=1e-12)
    assert stats["attributed_uj"] == pytest.approx(
        stats["prefill_uj"] + stats["decode_uj"] + stats["pages_uj"]
        + stats["retention_uj"], rel=1e-12)
    assert stats["attributed_uj"] == pytest.approx(
        sum(r.energy_uj for r in requests), rel=1e-9)
    assert all(r.energy_uj >= 0.0 for r in requests)


# ---------------------------------------------------------------------------
# Operating points and the meter in isolation
# ---------------------------------------------------------------------------


def test_operating_point_registry_and_validation():
    nominal = energy.operating_point("nominal")
    assert nominal.voltage == 0.8
    # 0.8 V is the calibration anchor: both scales are exactly 1 there
    assert nominal.leak_scale == pytest.approx(1.0)
    assert nominal.dyn_scale == pytest.approx(1.0)
    top = energy.operating_point("max")
    assert top.freq_mhz > nominal.freq_mhz and top.voltage > nominal.voltage
    with pytest.raises(ValueError, match="unknown operating point"):
        energy.operating_point("turbo")
    with pytest.raises(ValueError, match="unknown operating point"):
        EnergyMeter(point="turbo")


def test_meter_projection_matches_calibrated_dvfs_ratio():
    """Marginal joules/token at max vs nominal must land on the paper's
    §IV-D energy-per-work ratio (dvfs_ratios()[2], ~2.1x) — the meter
    derives it from the same leak/dyn scaling laws, so a drift here means
    the meter and the calibrated model diverged."""
    meter = EnergyMeter(point="max")
    at_max = meter.projected_uj_per_token()
    meter.set_point("nominal")
    at_nominal = meter.projected_uj_per_token()
    assert meter.dvfs_switches == 1
    meter.set_point("nominal")             # no-op: same point, no switch
    assert meter.dvfs_switches == 1
    _, _, energy_ratio = energy.dvfs_ratios()
    assert at_max / at_nominal == pytest.approx(energy_ratio, rel=0.02)


def test_unmetered_engine_has_no_energy_surface():
    eng, clock = make_engine(metered=False)
    reqs = make_requests(2, prompt_len=3, new_tokens=3)
    Simulator(eng, burst_trace(reqs), clock).run()
    assert "energy" not in eng.stats()
    assert all(r.energy_uj == 0.0 for r in reqs)
    with pytest.raises(ValueError, match="metered=False"):
        eng.set_operating_point("nominal")


# ---------------------------------------------------------------------------
# Conservation and attribution properties
# ---------------------------------------------------------------------------


@pytest.mark.properties
@settings(max_examples=8, deadline=None)
@given(n=st.integers(min_value=1, max_value=5),
       prompt_len=st.integers(min_value=2, max_value=6),
       new_tokens=st.integers(min_value=1, max_value=6),
       gap=st.sampled_from([0.0, 1.0, 2.5]),
       point=st.sampled_from(["max", "nominal"]),
       gate=st.booleans(), paged=st.booleans())
def test_energy_conservation_over_random_traces(n, prompt_len, new_tokens,
                                                gap, point, gate, paged):
    """Σ per-request joules + accounted overhead ≡ total platform energy,
    over randomised trace shapes, operating points, gating modes, and
    backends — and the simulator report agrees with the meter."""
    eng, clock = make_engine(slots=3, max_len=32, paged=paged,
                             page_size=8 if paged else None,
                             operating_point=point, gate_idle_banks=gate)
    reqs = make_requests(n, prompt_len=prompt_len, new_tokens=new_tokens)
    report = Simulator(eng, staggered_trace(reqs, gap=gap), clock).run()
    assert len(report.completed) == n
    _assert_conserved(eng, reqs)
    stats = eng.stats()["energy"]
    assert stats["point"] == point
    # fresh engine: the report's delta is the meter's lifetime total
    assert report.energy_uj == pytest.approx(stats["total_uj"], rel=1e-12)
    assert report.tokens_per_joule > 0
    # every request that produced tokens carries a positive attribution
    assert all(r.energy_uj > 0.0 for r in report.completed)


@pytest.mark.parametrize("backend", ["paged-async", "paged-sync", "lanes",
                                     "windowed"])
def test_metering_never_changes_tokens(backend):
    """Bit-identity across the meter's entire configuration space: off,
    default, DVFS-throttled, and ungated idle banks must all produce the
    same tokens on every backend."""
    kwargs = {"paged-async": dict(page_size=8, async_dispatch=True),
              "paged-sync": dict(page_size=8),
              "lanes": dict(paged=False)}

    def drive(**meter_kw):
        if backend == "windowed":
            cfg0, params = smoke_params()
            cfg = dataclasses.replace(cfg0, name=f"{cfg0.name}-swa8",
                                      sliding_window=8)
            clock = FakeClock()
            eng = ContinuousBatchingEngine(
                cfg, params, slots=2, max_len=40, clock=clock, page_size=8,
                lane_batch=CANONICAL["lane_batch"],
                device_len=CANONICAL["device_len"], **meter_kw)
            reqs = [Request(id=f"w{i}",
                            prompt=[(3 * i + j) % 150 + 1 for j in range(12)],
                            max_new_tokens=16)
                    for i in range(2)]
        else:
            eng, clock = make_engine(slots=3, max_len=32, **kwargs[backend],
                                     **meter_kw)
            reqs = make_requests(5, prompt_len=4, new_tokens=6)
        Simulator(eng, staggered_trace(reqs), clock).run()
        return tokens_of(eng)

    want = drive(metered=False)
    assert drive() == want
    assert drive(operating_point="nominal") == want
    assert drive(gate_idle_banks=False) == want


def test_energy_monotone_per_step():
    """All energy buckets — and every request's attribution — only ever
    grow as the engine steps."""
    eng, clock = make_engine(slots=2, max_len=32, page_size=8)
    reqs = make_requests(4, prompt_len=4, new_tokens=6)
    for r in reqs:
        r.arrival_time = clock.t
        assert eng.submit(r)
    prev = _counters(eng)
    prev_req = [r.energy_uj for r in reqs]
    while eng.busy:
        eng.step()
        clock.advance(0.5)
        cur = _counters(eng)
        cur_req = [r.energy_uj for r in reqs]
        assert all(b >= a for a, b in zip(prev, cur)), (prev, cur)
        assert all(b >= a for a, b in zip(prev_req, cur_req))
        prev, prev_req = cur, cur_req
    _assert_conserved(eng, reqs)


def test_retention_accrues_on_the_fake_clock():
    """Idle-retention is clock-time energy: a simulated run whose steps
    take time charges resident slots (and their held pages) between
    launches; the default frozen clock charges none."""
    eng, clock = make_engine(slots=2, max_len=32, page_size=8)
    reqs = make_requests(3, prompt_len=4, new_tokens=6)
    Simulator(eng, staggered_trace(reqs), clock, step_time=1.0).run()
    stats = eng.stats()["energy"]
    assert stats["retention_uj"] > 0.0
    assert stats["pages_uj"] > 0.0
    _assert_conserved(eng, reqs)

    frozen, _ = make_engine(slots=2, max_len=32, page_size=8)
    for r in make_requests(3, prompt_len=4, new_tokens=6):
        frozen.submit(r)
    frozen.run_until_idle()
    assert frozen.stats()["energy"]["retention_uj"] == 0.0


def test_shared_prefix_adopters_pay_less_than_the_payer():
    """Prefix sharing shows up in the attribution: the request that
    prefills the shared pages pays their compute; adopters skip it and
    split the holding cost 1/refcount, so each adopter's total is
    strictly below the payer's."""
    eng, clock = make_engine(slots=3, max_len=40, page_size=8,
                             prefill_chunk=4)
    reqs = shared_prefix_reqs("s", 4, prefix_len=16, tail_len=3,
                              new_tokens=4)
    Simulator(eng, staggered_trace(reqs), clock).run()
    _assert_conserved(eng, reqs)
    assert eng.prompt_tokens_reused > 0, "workload never shared"
    payer, *adopters = reqs
    assert all(payer.energy_uj > a.energy_uj for a in adopters), (
        [r.energy_uj for r in reqs])


def test_gating_and_dvfs_reduce_energy_not_tokens():
    """The benchmark's policy ordering, asserted at test scale: host-only
    burns more than clock-gated, nominal burns less than max — on
    bit-identical outputs."""
    def drive(**meter_kw):
        eng, clock = make_engine(slots=2, max_len=32, page_size=8,
                                 n_banks=4, **meter_kw)
        reqs = make_requests(4, prompt_len=4, new_tokens=6)
        report = Simulator(eng, staggered_trace(reqs, gap=2.0), clock).run()
        return tokens_of(eng), report.energy_uj

    gated_toks, gated = drive()
    host_toks, host_only = drive(gate_idle_banks=False)
    dvfs_toks, throttled = drive(operating_point="nominal")
    assert gated_toks == host_toks == dvfs_toks
    assert host_only > gated > throttled > 0.0


# ---------------------------------------------------------------------------
# Metrics and report plumbing
# ---------------------------------------------------------------------------


def test_serve_metrics_energy_summary():
    eng, clock = make_engine(slots=2, max_len=32)
    reqs = make_requests(4, prompt_len=3, new_tokens=5)
    report = Simulator(eng, staggered_trace(reqs), clock).run()
    m = ServeMetrics()
    m.observe_all(report.completed)
    out = m.summary(elapsed=report.elapsed)
    attributed = sum(r.energy_uj for r in report.completed)
    assert out["energy_uj_total"] == pytest.approx(attributed, rel=1e-12)
    assert out["energy_uj_p50"] <= out["energy_uj_p99"]
    assert out["uj_per_token"] == pytest.approx(
        attributed / out["total_tokens"], rel=1e-12)
    assert out["tokens_per_joule"] == pytest.approx(
        out["total_tokens"] / (attributed * 1e-6), rel=1e-12)

    unmetered = ServeMetrics()
    eng2, clock2 = make_engine(slots=2, max_len=32, metered=False)
    reqs2 = make_requests(4, prompt_len=3, new_tokens=5)
    rep2 = Simulator(eng2, staggered_trace(reqs2), clock2).run()
    unmetered.observe_all(rep2.completed)
    assert "energy_uj_p50" not in unmetered.summary()
    assert "tokens_per_joule" not in unmetered.summary()


def test_cluster_report_sums_engine_meters():
    cluster, clock = make_cluster(pool_pages=48, page_size=8)
    add_smoke_engine(cluster, name="a", namespace="granite")
    add_smoke_engine(cluster, name="b", namespace="granite",
                     metered=False)
    trace = (tag_engine(burst_trace(
                 make_requests(3, prompt_len=3, new_tokens=4,
                               prefix="a")), "a")
             + tag_engine(burst_trace(
                 make_requests(3, prompt_len=3, new_tokens=4,
                               prefix="b")), "b"))
    report = ClusterSimulator(cluster, trace, clock).run()
    meter = cluster.engines["a"]._meter
    assert report.energy_uj == pytest.approx(meter.total_uj, rel=1e-12)
    assert report.tokens_per_joule > 0
    agg = cluster.stats()["energy"]
    assert agg["metered_engines"] == 1
    assert agg["total_uj"] == pytest.approx(meter.total_uj, rel=1e-12)


def test_tenant_spec_stamps_energy_cap_without_perturbing_the_stream():
    """The cap rides on generated requests and costs zero RNG draws, so a
    capped trace is otherwise byte-identical to the uncapped one."""
    from repro.serve.loadgen import open_loop_trace

    with pytest.raises(ValueError, match="energy_cap"):
        TenantSpec(engine="e", energy_cap_uj_per_token=0.0)
    plain = TenantSpec(engine="e")
    capped = dataclasses.replace(plain, energy_cap_uj_per_token=3.0)
    a = list(open_loop_trace([plain], n_requests=50, rate=10.0, seed=7))
    b = list(open_loop_trace([capped], n_requests=50, rate=10.0, seed=7))
    assert all(x.request.energy_cap_uj_per_token is None for x in a)
    assert all(x.request.energy_cap_uj_per_token == 3.0 for x in b)
    assert [(x.time, x.request.prompt, x.request.max_new_tokens)
            for x in a] == [(x.time, x.request.prompt,
                             x.request.max_new_tokens) for x in b]


# ---------------------------------------------------------------------------
# Energy-aware policies
# ---------------------------------------------------------------------------


def _shed_drive(budget=None, request_cap=None, **eng_kw):
    cluster, clock = make_cluster(pool_pages=48, page_size=8,
                                  power_budget=budget)
    eng = add_smoke_engine(cluster, name="e", namespace="granite", **eng_kw)
    reqs = make_requests(3, prompt_len=3, new_tokens=4)
    if request_cap is not None:
        for r in reqs:
            r.energy_cap_uj_per_token = request_cap
    ClusterSimulator(cluster, tag_engine(burst_trace(reqs), "e"), clock).run()
    return cluster, eng


def test_energy_cap_sheds_above_projection_admits_below():
    # projected ~4.4 uJ/token at "max" busts a 3.0 cap: every head shed
    cluster, eng = _shed_drive(budget=PowerBudget(max_uj_per_token=3.0))
    assert cluster.energy_sheds == 3 and eng.shed == 3
    assert not eng.completed
    # the same cap at "nominal" (~2.1 uJ/token) admits everything
    cluster, eng = _shed_drive(budget=PowerBudget(max_uj_per_token=3.0),
                               operating_point="nominal")
    assert cluster.energy_sheds == 0 and len(eng.completed) == 3
    # an unmetered engine has no projection to compare: cap never binds
    cluster, eng = _shed_drive(budget=PowerBudget(max_uj_per_token=3.0),
                               metered=False)
    assert cluster.energy_sheds == 0 and len(eng.completed) == 3


def test_per_request_energy_cap_overrides_cluster_default():
    # a loose per-request cap wins over a busting cluster-wide default
    cluster, eng = _shed_drive(budget=PowerBudget(max_uj_per_token=3.0),
                               request_cap=10.0)
    assert cluster.energy_sheds == 0 and len(eng.completed) == 3
    # and a tight per-request cap sheds even without any cluster budget
    cluster, eng = _shed_drive(request_cap=1.0)
    assert cluster.energy_sheds == 3 and not eng.completed


def test_power_budget_dvfs_throttle_admits_instead_of_stalling():
    """With a throttle point, the first budget violation drops the engine
    to the lower DVFS point and admits; outputs stay bit-identical and
    the throttle is observable end to end (cluster counter, meter point,
    meter switch count)."""
    def reqs(prefix):
        return make_requests(4, prompt_len=3, new_tokens=4, prefix=prefix)

    want_a = standalone_tokens("granite_3_2b", reqs("a"))
    want_b = standalone_tokens("granite_3_2b", reqs("b"))
    cluster, clock = make_cluster(
        power_budget=PowerBudget(max_awake_banks=1,
                                 throttle_point="nominal"))
    ea = add_smoke_engine(cluster, name="x", namespace="granite")
    eb = add_smoke_engine(cluster, name="y", namespace="granite")
    sim = ClusterSimulator(
        cluster,
        tag_engine(burst_trace(reqs("a")), "x")
        + tag_engine(burst_trace(reqs("b")), "y"),
        clock)
    sim.run()
    assert cluster.dvfs_throttles >= 1
    switches = (ea._meter.dvfs_switches + eb._meter.dvfs_switches)
    assert switches == cluster.dvfs_throttles
    assert {"nominal"} >= {e._meter.point.name for e in (ea, eb)
                           if e._meter.dvfs_switches}
    assert tokens_of(ea) == want_a and tokens_of(eb) == want_b


def test_throttled_admission_is_exempt_without_a_throttle_point():
    """Without a throttle point the budget stalls exactly as before — the
    PR 10 levers must not change the default envelope semantics."""
    cluster, clock = make_cluster(
        power_budget=PowerBudget(max_awake_banks=1))
    add_smoke_engine(cluster, name="x", namespace="granite")
    add_smoke_engine(cluster, name="y", namespace="granite")
    sim = ClusterSimulator(
        cluster,
        tag_engine(burst_trace(make_requests(4, prefix="a")), "x")
        + tag_engine(burst_trace(make_requests(4, prefix="b")), "y"),
        clock)
    sim.run()
    assert cluster.power_stalls > 0
    assert cluster.dvfs_throttles == 0


# ---------------------------------------------------------------------------
# Attribution under preemption, replay, and crash recovery
# ---------------------------------------------------------------------------


def test_slo_preempt_replay_charges_energy_on_top():
    """A preempted-and-requeued request replays its prefix through the
    journal; the replayed device work is real work, so its attribution
    exceeds the undisturbed run's — while the tokens stay bit-identical
    and the journal records exactly one preemption."""
    def drive(policy):
        cluster, clock = make_cluster(pool_pages=48, page_size=8,
                                      policy=policy)
        eng = add_smoke_engine(cluster, name="g", namespace="granite",
                               slots=1, max_len=40)
        doomed = Request(id="long", prompt=[3, 4, 5], max_new_tokens=16,
                         slo=SLO(ttft=4.0, tpot=0.5))
        followers = make_requests(2, prompt_len=3, new_tokens=4, prefix="f")
        trace = tag_engine(burst_trace([doomed] + followers), "g")
        ClusterSimulator(cluster, trace, clock).run()
        _assert_conserved(eng, [doomed] + followers)
        return cluster, eng, doomed

    cluster, eng, doomed = drive(SchedPolicy(preempt_busted=True))
    assert doomed.slo_preempts == 1
    assert cluster.journal.journal("g").get("long").slo_preempts == 1
    _, plain_eng, undisturbed = drive(SchedPolicy())
    assert tokens_of(eng) == tokens_of(plain_eng)
    assert doomed.energy_uj > undisturbed.energy_uj


def test_crash_rebuild_carries_joules_and_counters_forward():
    """Kill engines with in-flight sampled and sliding-window requests:
    the rebuilt engines keep the same meter (accumulated joules and the
    operating point survive), every stats counter stays monotone across
    the crash, conservation holds over the replayed requests, and the
    recovered tokens are bit-identical to the fault-free run."""
    def build():
        # the watchdog keeps client request handles, so replay charges
        # land on the same objects the conservation sum ranges over
        cluster, clock = make_cluster(pool_pages=64, page_size=8,
                                      watchdog=FTConfig())
        add_smoke_engine(cluster, name="g", namespace="granite", slots=2,
                         max_len=40, prefill_chunk=2, page_size=8,
                         async_dispatch=True, operating_point="nominal")
        swa_cfg, swa_params = smoke_params()
        swa = dataclasses.replace(swa_cfg, name=f"{swa_cfg.name}-swa8",
                                  sliding_window=8)
        cluster.add_engine(swa, swa_params, name="w", namespace="swa",
                           slots=2, max_len=40,
                           lane_batch=CANONICAL["lane_batch"],
                           device_len=CANONICAL["device_len"])
        g = shared_prefix_reqs("s", 3, prefix_len=16, tail_len=3,
                               new_tokens=5)
        g += [Request(id=f"x{i}",
                      prompt=[(5 * i + j) % 200 + 1 for j in range(4)],
                      max_new_tokens=6,
                      sampling=dataclasses.replace(SAMPLED))
              for i in range(3)]
        w = [Request(id=f"w{i}",
                     prompt=[(3 * i + j) % 150 + 1 for j in range(12)],
                     max_new_tokens=16)
             for i in range(2)]
        trace = list(tag_engine(staggered_trace(g, gap=1.0), "g"))
        trace += list(tag_engine(staggered_trace(w, gap=1.0), "w"))
        trace.sort(key=lambda a: a.time)
        return cluster, clock, trace, {"g": g, "w": w}

    base, bclock, btrace, _ = build()
    ClusterSimulator(base, btrace, bclock).run()
    want = {n: tokens_of(e) for n, e in base.engines.items()}

    cluster, clock, trace, reqs = build()
    sim = ClusterSimulator(cluster, trace, clock)
    for _ in range(12):
        sim._deliver_due()
        if cluster.busy:
            cluster.step()
        clock.advance(1.0)
    assert cluster.engines["g"].active > 0
    assert cluster.engines["w"].active > 0
    pre = {n: _counters(e) for n, e in cluster.engines.items()}
    meters = {n: e._meter for n, e in cluster.engines.items()}
    cluster.crash_engine("g")
    cluster.crash_engine("w")
    for n, e in cluster.engines.items():
        assert e._meter is meters[n], "rebuild must keep the meter object"
        assert e._meter.point.name == ("nominal" if n == "g" else "max")
        post = _counters(e)
        assert all(b >= a for a, b in zip(pre[n], post)), (n, pre[n], post)
    sim.run()
    assert {n: tokens_of(e) for n, e in cluster.engines.items()} == want
    for n, e in cluster.engines.items():
        final = _counters(e)
        assert all(b >= a for a, b in zip(pre[n], final))
        _assert_conserved(e, reqs[n])


@pytest.mark.slow
def test_replica_member_crash_recovers_bit_identically(subproc):
    """PR 8 x PR 9 cross-feature: crash one tp=2 member of a 2-replica
    group mid-flight (4 forced host devices); the journal rebuild lands
    on the same sharded member, the recovered tokens match the standalone
    reference, no request double-completes, and each member's meter
    balances over its completed requests."""
    code = """
import sys; sys.path.insert(0, {tests!r})
import jax
assert len(jax.devices()) == 4, jax.devices()
import engine_sim as es
from repro.launch.mesh import replica_meshes
from repro.runtime.ft import FTConfig
from repro.serve.sampling import SamplingParams
from repro.serve.sim import ClusterSimulator, burst_trace, tag_engine

ARCH = "granite_3_2b"
cfg, params = es.smoke_params(ARCH)

def reqs():
    shared = es.shared_prefix_reqs("s", 6, prefix_len=16, tail_len=3,
                                   new_tokens=5)
    distinct = es.make_requests(6, prompt_len=5, new_tokens=5, prefix="d")
    for r in distinct[::2]:
        r.sampling = SamplingParams(temperature=0.9, top_k=7)
    return shared + distinct

ref = es.standalone_tokens(ARCH, reqs(), slots=3, max_len=40, page_size=8)

cluster, clock = es.make_cluster(pool_pages=96, page_size=8,
                                 watchdog=FTConfig())
members = cluster.add_replica_group(cfg, params, name="gran", slots=3,
                                    max_len=40, meshes=replica_meshes(2, 2),
                                    lane_batch=4, device_len=48)
sim = ClusterSimulator(cluster, tag_engine(burst_trace(reqs()), "gran"),
                       clock)
for _ in range(6):                      # run partway: work is in flight
    sim._deliver_due()
    if cluster.busy:
        cluster.step()
    clock.advance(1.0)
victim = max(members, key=lambda n: cluster.engines[n].active)
assert cluster.engines[victim].active > 0, "nothing in flight to recover"
pre_uj = {{n: cluster.engines[n]._meter.total_uj for n in members}}
cluster.crash_engine(victim)
assert cluster.crashes == cluster.rebuilds == 1
assert cluster.engines[victim]._meter.total_uj >= pre_uj[victim]
sim.run()                               # drain through the rebuilt member

got = {{}}
for n in members:
    got.update(es.tokens_of(cluster.engines[n]))
assert got == ref, {{k: (got.get(k), ref[k]) for k in ref
                     if got.get(k) != ref[k]}}
for n in members:
    eng = cluster.engines[n]
    ids = [r.id for r in eng.completed]
    assert len(ids) == len(set(ids)), "double completion"
    stats = eng.stats()["energy"]
    attributed = sum(r.energy_uj for r in eng.completed)
    assert abs(stats["attributed_uj"] - attributed) <= 1e-9 * max(
        stats["attributed_uj"], 1.0), (n, stats["attributed_uj"], attributed)
    assert stats["total_uj"] >= pre_uj[n]
print("CHAOS_TP_OK")
""".format(tests=TESTS)
    assert "CHAOS_TP_OK" in subproc(code, devices=4)
