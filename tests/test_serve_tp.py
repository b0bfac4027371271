"""Tensor-parallel + replica-group serving: multi-device subprocess tests.

The sharded-serving acceptance gates, each in a fresh process with forced
host devices (the main test process keeps 1 device):

* tp=2 head-sharded paged decode is bit-identical to the single-device
  engine for **every** paged-capable config — greedy and sampled, sync
  and async-dispatch, windowed (h2o danube) and not, modality stubs
  included. Sharding is a memory/latency move, never an output move.
* the sharded engine refuses configs the rule engine rejects (MoE here;
  the full rejection matrix lives in ``test_serve_tp_rules.py``).
* a 2x tp=2 replica group behind one ``ServeCluster`` target serves a
  seeded trace bit-identically to a standalone engine, with prefix
  affinity co-locating shared-prefix requests and the pool arenas split
  across all 4 devices — and the whole thing passes the PR 6 gate:
  driving the same seeded open-loop trace twice is bit-identical.
* draining a replica mid-flight migrates its journal records and queue
  onto siblings and the migrated requests complete bit-identically.

Mirrors the ``test_registry_serve.py`` tiering: granite stays in the
fast tier, the long tail and the 4-device tests run ``slow``.
"""

import pathlib

import pytest

from repro import configs
from repro.models import registry

TESTS = str(pathlib.Path(__file__).resolve().parent)

# head-sharded serving: paged configs without per-slot recurrent state
PAGED = [a for a in configs.names()
         if registry.supports_paged(configs.smoke(a))
         and not registry.holds_state(configs.smoke(a))]
_FAST = {"granite_3_2b"}


def _tiered(names):
    return [a if a in _FAST else pytest.param(a, marks=pytest.mark.slow)
            for a in names]


_TP_BITID = """
import sys; sys.path.insert(0, {tests!r})
import jax
assert len(jax.devices()) == 2, jax.devices()
import engine_sim as es
from repro.launch.mesh import serve_tp_mesh
from repro.serve.sampling import SamplingParams

def reqs():
    rs = es.make_requests(4, prompt_len=5, new_tokens=4)
    rs[1].sampling = SamplingParams(temperature=0.8, top_p=0.9, seed=7)
    rs[3].sampling = SamplingParams(temperature=1.1, top_k=5, seed=11)
    return rs

for async_dispatch in (False, True):
    kw = dict(slots=2, max_len=32, async_dispatch=async_dispatch)
    ref = es.standalone_tokens({arch!r}, reqs(), **kw)
    got = es.standalone_tokens({arch!r}, reqs(), mesh=serve_tp_mesh(2), **kw)
    assert set(ref) == {{"r0", "r1", "r2", "r3"}}, ref
    assert got == ref, ("tp2 diverged", async_dispatch,
                        {{k: (got.get(k), ref[k]) for k in ref
                          if got.get(k) != ref[k]}})
print("TP_BITID_OK")
"""


@pytest.mark.parametrize("arch", _tiered(PAGED))
def test_tp2_bit_identical_to_single_device(arch, subproc):
    """Greedy + two sampled streams, sync and async dispatch: the
    head-sharded decode on a forced 2-device mesh reproduces the
    single-device engine token for token."""
    code = _TP_BITID.format(tests=TESTS, arch=arch)
    assert "TP_BITID_OK" in subproc(code, devices=2)


@pytest.mark.slow
def test_tp_mesh_rejects_lane_fallback_config(subproc):
    """The engine refuses to build a sharded MoE engine — the rule
    engine's rejection surfaces at construction, not as a silent lane
    fallback that ignores the mesh."""
    code = """
import sys; sys.path.insert(0, {tests!r})
import engine_sim as es
from repro.launch.mesh import serve_tp_mesh
from repro.serve.engine import ContinuousBatchingEngine

cfg, params = es.smoke_params("grok_1_314b")
try:
    ContinuousBatchingEngine(cfg, params, slots=2, max_len=32,
                             mesh=serve_tp_mesh(2))
except ValueError as e:
    assert "cannot serve tensor-parallel" in str(e), e
    print("TP_REJECT_OK")
else:
    raise AssertionError("sharded MoE engine built silently")
""".format(tests=TESTS)
    assert "TP_REJECT_OK" in subproc(code, devices=2)


_REPLICA_COMMON = """
import sys; sys.path.insert(0, {tests!r})
import jax
assert len(jax.devices()) == 4, jax.devices()
import engine_sim as es
from repro.launch.mesh import replica_meshes
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
"""


@pytest.mark.slow
def test_replica_group_bit_identical_and_split(subproc):
    """2x tp=2 replicas behind one group name: bit-identical to the
    standalone engine, both replicas served, shared-prefix requests
    co-located by affinity, arenas resident on all 4 devices — and the
    same trace driven twice (PR 6 open-loop determinism gate) lands
    every request on the same replica with the same tokens."""
    code = _REPLICA_COMMON.format(tests=TESTS) + """
def drive():
    cluster, clock = es.make_cluster(pool_pages=96, page_size=8)
    members = cluster.add_replica_group(cfg, params, name="gran", slots=3,
                                        max_len=40,
                                        meshes=replica_meshes(2, 2),
                                        lane_batch=4, device_len=48)
    trace = tag_engine(burst_trace(reqs()), "gran")
    ClusterSimulator(cluster, trace, clock).run()
    toks = {}
    for n in members:
        toks.update(es.tokens_of(cluster.engines[n]))
    by_member = {n: sorted(r.id for r in cluster.engines[n].completed)
                 for n in members}
    return cluster, members, toks, by_member

cluster, members, got, by_member = drive()
assert got == ref, {k: (got.get(k), ref[k]) for k in ref
                    if got.get(k) != ref[k]}
assert all(by_member.values()), by_member
# prefix affinity: every shared-prefix request lands on one home replica
homes = [n for n, ids in by_member.items()
         if any(i.startswith("s") for i in ids)]
assert len(homes) == 1, by_member
by_dev = cluster.pool.bytes_by_device()
assert len(by_dev) == 4 and len(set(by_dev.values())) == 1, by_dev

# PR 6 determinism gate: a second fresh drive is bit-identical, same homes
_, _, got2, by_member2 = drive()
assert got2 == got and by_member2 == by_member
print("REPLICA_OK")
"""
    assert "REPLICA_OK" in subproc(code, devices=4)


@pytest.mark.slow
def test_drain_replica_migrates_bit_identically(subproc):
    """Mid-flight drain: the victim's journal records and queue move to
    the sibling, every migrated request finishes with the reference
    tokens, and the victim's page namespace is fully evicted."""
    code = _REPLICA_COMMON.format(tests=TESTS) + """
cluster, clock = es.make_cluster(pool_pages=96, page_size=8)
members = cluster.add_replica_group(cfg, params, name="g2", slots=2,
                                    max_len=40, meshes=replica_meshes(2, 2),
                                    lane_batch=4, device_len=48)
rs = reqs()
for r in rs:
    r.arrival_time = clock.t
    assert cluster.submit("g2", r)
for _ in range(4):                      # tokens in flight on both members
    cluster.step()
victim = members[0]
pre_done = {r.id for r in cluster.engines[victim].completed}
moved = cluster.drain_replica("g2", victim)
assert victim not in cluster.engines
assert victim not in cluster.stats()["groups"]["g2"]
assert cluster.migrations > 0, "drain migrated nothing in-flight"
# the victim only owned its routed share; all of it must have moved
assert sum(len(v) for v in moved.values()) > 0, moved
cluster.run_until_idle()
got = {}
for n in members[1:]:
    got.update(es.tokens_of(cluster.engines[n]))
for rid in pre_done:                    # finished-before-drain stay put
    got.setdefault(rid, ref[rid])
assert got == ref, {k: (got.get(k), ref[k]) for k in ref
                    if got.get(k) != ref[k]}
assert not any(ns.endswith("@r0") for ns in cluster.table.resident_by_ns())
print("MIGRATE_OK")
"""
    assert "MIGRATE_OK" in subproc(code, devices=4)
