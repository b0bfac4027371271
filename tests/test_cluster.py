"""Multi-model serving cluster: shared pool/table, budget, replay, fairness.

Everything runs under the deterministic harness (fake clock, scripted
traces, tiny smoke models). The invariants held here are the cluster
analogue of the engine suite's:

* **Per-engine bit-identity** — a request's tokens are the same whether
  its engine serves alone (private pool/table) or as a cluster tenant
  (shared pool/table, cross-engine prefix aliasing, admission stalls).
* **The power budget is never exceeded** — admissions stall instead, and
  preempt/replay under a budget stays bit-identical per engine.
* **Pool invariants survive multi-tenancy** — the property test drives
  random interleaved acquire/release/adopt across two tenants and checks
  the free list and refcounts never leak or go negative.
"""

import pytest

from hypothesis import given, settings, strategies as st

from engine_sim import (ClusterSimulator, FakeClock, PowerBudget, Request,
                        Simulator, add_smoke_engine, burst_trace,
                        make_cluster, shared_prefix_requests, smoke_params,
                        staggered_trace, tag_engine)
from repro.serve.paged import PagePool


# shared helpers live in engine_sim (PR 7 hygiene: one definition for the
# five suites that compare completed-token maps)
from engine_sim import shared_prefix_reqs as _reqs
from engine_sim import standalone_tokens as _standalone
from engine_sim import tokens_of as _tokens


# -- the tentpole: two models, one pool, one table -----------------------------


def test_two_models_one_pool_bit_identical_and_paged():
    """Two different model configs on one cluster share a single
    PagePool/PageTable, both stay on the paged backend (the old
    shared-table lane fallback is gone), both reuse prefix pages, and
    outputs are bit-identical to each engine serving alone."""
    want_g = _standalone("granite_3_2b", _reqs("g"))
    want_s = _standalone("stablelm_3b", _reqs("s"))
    cluster, clock = make_cluster()
    eg = add_smoke_engine(cluster, "granite_3_2b", name="granite")
    es = add_smoke_engine(cluster, "stablelm_3b", name="stablelm")
    assert eg._pool is cluster.pool and es._pool is cluster.pool
    assert eg.pages is cluster.table and es.pages is cluster.table
    trace = (tag_engine(burst_trace(_reqs("g")), "granite")
             + tag_engine(burst_trace(_reqs("s")), "stablelm"))
    rep = ClusterSimulator(cluster, trace, clock).run()
    assert rep.tokens_generated == 2 * 4 * 4
    assert eg.stats()["backend"] == "paged"      # shared table, still paged
    assert es.stats()["backend"] == "paged"
    assert _tokens(eg) == want_g and _tokens(es) == want_s
    assert eg.prompt_tokens_reused > 0 and es.prompt_tokens_reused > 0
    by_ns = cluster.table.resident_by_ns()
    assert set(by_ns) == {"granite-smoke", "stablelm-smoke"}


def test_cross_engine_replica_prefix_reuse():
    """Two engines serving the same model under one namespace: the second
    replica's cold requests adopt pages the first replica published —
    prefix sharing across engines, bit-identical outputs."""
    want_a = _standalone("granite_3_2b", _reqs("a"))
    want_b = _standalone("granite_3_2b", _reqs("b"))
    cluster, clock = make_cluster()
    ea = add_smoke_engine(cluster, name="rep-a", namespace="granite")
    eb = add_smoke_engine(cluster, name="rep-b", namespace="granite")
    for r in _reqs("a"):
        cluster.submit("rep-a", r)
    cluster.run_until_idle()
    published = cluster.table.stats["published"]
    assert published > 0
    for r in _reqs("b"):
        cluster.submit("rep-b", r)
    cluster.run_until_idle()
    # replica b found every shared page resident: nothing new published,
    # and even its first request was admitted with the prefix pre-consumed
    assert cluster.table.stats["published"] == published
    assert cluster.journal.journal("rep-b").get("b0").prefix_reused == 16
    assert eb.prompt_tokens_reused >= 4 * 16
    assert _tokens(ea) == want_a and _tokens(eb) == want_b


def test_namespaces_isolate_different_weights():
    """Same config, different weights, different namespaces: identical
    token prefixes must NOT alias across the namespace boundary (the same
    tokens under different weights are different states)."""
    cluster, _ = make_cluster()
    add_smoke_engine(cluster, name="m0", namespace="m0", seed=0)
    eb = add_smoke_engine(cluster, name="m1", namespace="m1", seed=1)
    for r in _reqs("a"):
        cluster.submit("m0", r)
    cluster.run_until_idle()
    # m0's prefix pages are resident under ns "m0"; m1 sees a cold table
    for r in _reqs("b"):
        cluster.submit("m1", r)
    cluster.run_until_idle()
    assert cluster.journal.journal("m1").get("b0").prefix_reused == 0
    by_ns = cluster.table.resident_by_ns()
    assert by_ns["m0"] > 0 and by_ns["m1"] > 0
    assert _tokens(eb) == _standalone("granite_3_2b", _reqs("b"), seed=1)


def test_same_namespace_different_model_rejected():
    """Namespace peers alias pages bitwise, so a namespace may only ever
    serve one (config, weights) identity."""
    cluster, _ = make_cluster()
    add_smoke_engine(cluster, name="a", namespace="shared", seed=0)
    with pytest.raises(ValueError, match="different model"):
        add_smoke_engine(cluster, name="b", namespace="shared", seed=1)
    with pytest.raises(ValueError, match="different model"):
        add_smoke_engine(cluster, "stablelm_3b", name="c", namespace="shared")
    # distinct namespace with the distinct model is fine
    add_smoke_engine(cluster, "stablelm_3b", name="d")
    # and duplicate target names (engine or replica group) are not
    with pytest.raises(ValueError, match="duplicate target name"):
        add_smoke_engine(cluster, name="a", namespace="granite")


def test_lane_only_family_cannot_join_cluster():
    """The shared pool holds transformer KV pages; an SSM config has no
    paged decode and must be rejected loudly."""
    cluster, _ = make_cluster()
    with pytest.raises(ValueError, match="paged"):
        add_smoke_engine(cluster, "mamba2_370m", name="ssm")


# -- power-budget backpressure -------------------------------------------------


def test_power_budget_stalls_admissions_never_exceeds():
    """With a 1-bank budget the cluster keeps at most one bank awake at
    every instant, stalls admissions (observably) instead of exceeding it,
    and still drains the trace bit-identically."""
    want_a = _standalone("granite_3_2b", _reqs("a"))
    want_b = _standalone("granite_3_2b", _reqs("b"))
    cluster, clock = make_cluster(
        power_budget=PowerBudget(max_awake_banks=1))
    ea = add_smoke_engine(cluster, name="x", namespace="granite")
    eb = add_smoke_engine(cluster, name="y", namespace="granite")
    sim = ClusterSimulator(
        cluster,
        tag_engine(burst_trace(_reqs("a")), "x")
        + tag_engine(burst_trace(_reqs("b")), "y"),
        clock)
    max_awake = 0
    while cluster.busy or sim.pending:
        sim._deliver_due()
        if cluster.busy:
            cluster.step()
            clock.advance(1.0)
        max_awake = max(max_awake, cluster.awake_banks())
    assert max_awake == 1
    assert cluster.power_stalls > 0
    assert ea.admission_stalls + eb.admission_stalls >= cluster.power_stalls
    assert _tokens(ea) == want_a and _tokens(eb) == want_b


def test_power_budget_preempt_replay_bit_identical():
    """preempt() + replay under a constrained budget reproduces every
    engine's tokens bit-for-bit (per-engine journals cross-check)."""
    want_a = _standalone("granite_3_2b", _reqs("a"))
    want_b = _standalone("granite_3_2b", _reqs("b"))
    cluster, _ = make_cluster(power_budget=PowerBudget(max_awake_banks=1))
    ea = add_smoke_engine(cluster, name="x", namespace="granite")
    eb = add_smoke_engine(cluster, name="y", namespace="granite")
    for r in _reqs("a"):
        cluster.submit("x", r)
    for r in _reqs("b"):
        cluster.submit("y", r)
    for _ in range(5):
        cluster.step()                        # mid-flight on both tenants
    requeued = cluster.preempt()
    assert any(requeued.values())
    assert all(e.active == 0 for e in cluster.engines.values())
    assert cluster.table.pinned == 0
    cluster.run_until_idle()
    assert _tokens(ea) == want_a and _tokens(eb) == want_b


def test_power_veto_skips_to_slot_on_awake_bank():
    """A per-slot power veto must not end the round: a later free slot
    whose bank is already awake admits the same head request at zero
    budget cost (slots 0 and 2 share bank0 here; slots 1 and 3 would wake
    bank1 and stay vetoed)."""
    from repro.core.platform import Platform, XHeepConfig

    platform = Platform(XHeepConfig(n_banks=2))
    for i in range(2):
        platform.power.clock_gate(f"bank{i}")
    cluster, _ = make_cluster(platform=platform,
                              power_budget=PowerBudget(max_awake_banks=1))
    eng = add_smoke_engine(cluster, name="x", slots=4)
    for r in _reqs("p", 3):
        cluster.submit("x", r)
    cluster.step()
    occupied = [i for i, s in enumerate(eng.slots) if s is not None]
    assert occupied == [0, 2]                  # both bank0, one wake total
    assert cluster.awake_banks() == 1
    assert cluster.power_stalls > 0            # slots 1/3 were vetoed
    cluster.run_until_idle()
    assert len(eng.completed) == 3


def test_impossible_budget_raises_instead_of_spinning():
    """A budget no admission can ever satisfy must fail loudly (budget
    deadlock), not stall the cluster forever."""
    cluster, _ = make_cluster(
        power_budget=PowerBudget(budget_uw=-1.0))   # nothing fits
    add_smoke_engine(cluster, name="x")
    cluster.submit("x", Request(id="r", prompt=[1, 2], max_new_tokens=1))
    with pytest.raises(RuntimeError, match="budget deadlock"):
        cluster.run_until_idle()


def test_power_budget_validation():
    with pytest.raises(ValueError,
                       match="max_awake_banks, budget_uw, or max_uj"):
        PowerBudget()
    with pytest.raises(ValueError, match=">= 1"):
        PowerBudget(max_awake_banks=0)
    with pytest.raises(ValueError, match="max_uj_per_token"):
        PowerBudget(max_uj_per_token=0.0)
    with pytest.raises(ValueError, match="unknown operating point"):
        PowerBudget(max_awake_banks=1, throttle_point="turbo")


def test_wrr_weight_paces_admissions_per_round():
    """weight=1 on a 4-slot engine admits at most one request per
    scheduling round (the stall is observable and FIFO-preserving);
    the default weight (= slots) fills every free slot at once."""
    cluster, _ = make_cluster()
    paced = add_smoke_engine(cluster, name="paced", slots=4, weight=1)
    for r in _reqs("p"):
        cluster.submit("paced", r)
    cluster.step()
    assert paced.active == 1 and paced.admission_stalls > 0
    cluster.step()
    assert paced.active == 2
    assert cluster.wrr_stalls > 0
    cluster.run_until_idle()
    assert len(paced.completed) == 4
    # admissions were spread over rounds in FIFO order
    seqs = [cluster.journal.journal("paced").get(f"p{i}").arrival_seq
            for i in range(4)]
    assert seqs == sorted(seqs)

    cluster2, _ = make_cluster()
    eager = add_smoke_engine(cluster2, name="eager", slots=4)   # weight=slots
    for r in _reqs("e"):
        cluster2.submit("eager", r)
    cluster2.step()
    assert eager.active == 4 and eager.admission_stalls == 0


# -- shared-pool pressure ------------------------------------------------------


def test_pool_pressure_reclaims_fairly_and_serves_correctly():
    """A pool too small to hold every tenant's residency reclaims idle
    pages (heaviest namespace first) instead of failing or wiping every
    tenant, and outputs stay bit-identical."""
    from engine_sim import make_requests

    reqs_a = lambda: make_requests(6, prompt_len=25, prefix="a")
    reqs_b = lambda: make_requests(6, prompt_len=25, prefix="b")
    want_a = _standalone("granite_3_2b", reqs_a())
    want_b = _standalone("granite_3_2b", reqs_b(), seed=1)
    # distinct 25-token prompts publish 3 resident pages each; worst-case
    # concurrent block-table demand is 16 (4 slots x 4 pages), so a
    # 17-page pool forces reclaim of idle residency as waves turn over
    cluster, clock = make_cluster(pool_pages=17)
    ea = add_smoke_engine(cluster, name="x", namespace="granite")
    eb = add_smoke_engine(cluster, name="y", namespace="other", seed=1)
    trace = (tag_engine(burst_trace(reqs_a()), "x")
             + tag_engine(burst_trace(reqs_b()), "y"))
    ClusterSimulator(cluster, trace, clock).run()
    assert sum(cluster.reclaims.values()) > 0
    assert cluster.pool.in_use <= cluster.pool.n_pages
    assert _tokens(ea) == want_a and _tokens(eb) == want_b


# -- cluster sim mechanics -----------------------------------------------------


def test_cluster_sim_one_clock_per_engine_reports():
    """One fake clock drives every tenant; the report splits completions
    per engine and sums tokens; untagged arrivals are rejected."""
    cluster, clock = make_cluster()
    add_smoke_engine(cluster, name="granite")
    add_smoke_engine(cluster, "stablelm_3b", name="stablelm")
    trace = (tag_engine(staggered_trace(_reqs("g", 3), gap=2.0), "granite")
             + tag_engine(staggered_trace(_reqs("s", 3), gap=3.0),
                          "stablelm"))
    rep = ClusterSimulator(cluster, trace, clock).run()
    assert set(rep.completed) == {"granite", "stablelm"}
    assert [r.id for r in rep.completed["granite"]] == ["g0", "g1", "g2"]
    assert [r.id for r in rep.completed["stablelm"]] == ["s0", "s1", "s2"]
    assert rep.tokens_generated == 6 * 4
    assert rep.elapsed > 0 and rep.throughput > 0
    finish = [r.finish_time for r in rep.completed["granite"]]
    assert finish == sorted(finish)
    with pytest.raises(ValueError, match="untagged arrival"):
        ClusterSimulator(cluster,
                         staggered_trace(_reqs("u", 1)), clock)


def test_cluster_journal_keeps_engines_separate():
    cluster, clock = make_cluster()
    add_smoke_engine(cluster, name="a", namespace="granite")
    add_smoke_engine(cluster, name="b", namespace="granite")
    trace = (tag_engine(burst_trace(_reqs("a", 2)), "a")
             + tag_engine(burst_trace(_reqs("b", 2)), "b"))
    ClusterSimulator(cluster, trace, clock).run()
    done = cluster.journal.completed()
    assert set(done) == {"a", "b"}
    assert [r.request_id for r in done["a"]] == ["a0", "a1"]
    assert [r.request_id for r in done["b"]] == ["b0", "b1"]
    assert not cluster.journal.incomplete()


# -- PagePool invariants under multi-tenant interleaving (property test) -------


@settings(max_examples=25, deadline=None)
@given(codes=st.lists(st.integers(min_value=0, max_value=10**6),
                      min_size=1, max_size=120),
       n_pages=st.integers(min_value=2, max_value=9))
def test_pool_invariants_random_two_tenant_interleaving(codes, n_pages):
    """Random interleaved alloc/adopt/release across two tenants: the free
    list and refcounts never leak or go negative, per-tenant accounting
    sums to the pool's occupancy, and the null sentinel is never a real
    page."""
    pool = PagePool(n_pages, 4)
    refs: dict[int, int] = {}
    held = {"a": [], "b": []}
    for code in codes:
        tenant = "a" if (code // 7) % 2 == 0 else "b"
        op = code % 3
        if op == 0:                                  # alloc
            if pool.free_count:
                idx = pool.alloc(tenant)
                assert idx != pool.null
                assert refs.get(idx, 0) == 0
                refs[idx] = 1
                held[tenant].append(idx)
            else:
                with pytest.raises(RuntimeError, match="exhausted"):
                    pool.alloc(tenant)
        elif op == 1:                                # adopt (cross-tenant pin)
            live = sorted(i for i, c in refs.items() if c > 0)
            if live:
                idx = live[code % len(live)]
                pool.retain(idx)
                refs[idx] += 1
                held[tenant].append(idx)
        else:                                        # release one we hold
            if held[tenant]:
                idx = held[tenant].pop(code % len(held[tenant]))
                pool.release(idx)
                refs[idx] -= 1
        # invariants after every operation
        assert pool.in_use + pool.free_count == pool.n_pages
        assert pool.refcounts() == {i: c for i, c in refs.items() if c > 0}
        assert sum(pool.owners().values()) == pool.in_use
    # the null sentinel is not a refcounted page
    with pytest.raises(ValueError):
        pool.retain(pool.null)
    with pytest.raises(ValueError):
        pool.release(pool.null)
    # drain everything: the pool must return to fully free, nothing leaked
    for tenant in held:
        for idx in held[tenant]:
            pool.release(idx)
    assert pool.in_use == 0 and pool.free_count == pool.n_pages
    assert pool.stats["allocated"] == pool.stats["freed"]
    assert not pool.owners()
    if n_pages:                                      # over-release raises
        with pytest.raises(ValueError, match="released more"):
            pool.release(0)
