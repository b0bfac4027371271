"""Arrivals-trace serving benchmark: paged decode + async dispatch vs PR 2.

Replays a deterministic trace of staggered request arrivals through the
continuous-batching engine and reports tokens/s on the simulation clock
plus wall-clock step latency. The sim cost model charges ``--dispatch-time``
of host scheduling plus ``--step-time`` of device compute per engine step;
a synchronous engine pays them serially, the async double-buffered engine
overlaps them (see :class:`repro.serve.sim.Simulator`). Modes:

* default — the new engine (paged KV pool + async dispatch) vs the PR 2
  engine (per-slot cache lanes, synchronous dispatch) vs one-request-at-a-
  time serving, all on the same trace. Outputs are asserted bit-identical
  across all three before any number is reported.
* ``--shared-prefix [N]`` — every request's prompt shares an N-token
  prefix; paged sharing (block-table adoption, mid-flight re-match, cold-
  prefill dedup) is compared against the same engine with sharing off and
  against the PR 2 sharing engine.
* ``--sliding-window [W]`` — a sliding-window config on the paged backend
  (ring block tables): the windowed paged engine vs the PR 2-style lane
  ring cache on a trace whose requests run well past the window. Outputs
  are asserted bit-identical (including the ring recycling), and the
  report carries the memory story: table entries per slot
  (``ceil(window/page_size) + 1`` vs the unwindowed ``ceil(max_len/
  page_size)``), the pool pages provisioned, and pages recycled.
* ``--kernel-bench`` — microbenchmark of the fused paged-attention Pallas
  kernel (interpret mode on CPU) against its pure-jax reference.
* ``--tp [N]`` — mesh-sharded serving: single device vs N-way
  tensor-parallel paged decode (pool arenas and attention heads sharded
  over a ``("model",)`` mesh under ``shard_map``) vs a 2-replica group of
  N-way members on disjoint device slices. Bit-identity is asserted
  across all three, and the report shows the arenas *split* (1/N of the
  single-device bytes per device), not duplicated. Needs forced host
  devices: ``XLA_FLAGS=--xla_force_host_platform_device_count=2N``.
* ``--open-loop [N]`` — N lazily generated open-loop arrivals (seeded
  bursty/Poisson/diurnal process, default 10⁵) at an offered load far
  above cluster capacity: SLO-aware scheduling (DRR over ``step_cost`` +
  TTFT shedding + deadline preemption) vs flat WRR, compared on goodput,
  p50/p99 TTFT, per-token latency, and SLO attainment. Two same-seed SLO
  runs are asserted bit-identical before any number is reported.
* ``--sampling [N]`` — deterministic stochastic sampling: N open-loop
  arrivals (default 2000) whose tenants carry per-request seeded
  :class:`~repro.serve.sampling.SamplingParams` through the full
  SLO-aware policy (shedding + preempt-and-requeue). Two same-seed
  sampled runs are asserted bit-identical, sampled streams must diverge
  from a greedy drive of the byte-identical arrivals, and the greedy
  control tenant's streams must not.
* ``--chaos [N]`` — chaos-tolerant serving: the sampling topology driven
  fault-free, then under a seeded
  :class:`~repro.serve.chaos.FaultPlan` (device-step failures, corrupted
  tokens, NaN logits, allocation failures, engine crashes, bank
  power-faults, prefix drops), then under the same plan again. Built-in
  assertions: completed requests are bit-identical to the fault-free
  run, no request is lost or double-completed, and the two same-seed
  chaos runs agree end to end. Reports recovery overhead and goodput
  retention under faults.
* ``--multi-model`` — the PR 4 cluster workload: two models / three
  engines (two replicas of one model sharing a namespace, plus a second
  model) on one ``ServeCluster`` — one shared ``PagePool``/``PageTable``
  — against the same three engines serving the same traffic isolated
  (private pools/tables). Outputs are asserted bit-identical per engine
  before any number is reported; the report carries cross-engine page
  reuse and the consolidated pool high-water vs the isolated pools.

``--json`` prints the report as JSON; ``--bench-json`` additionally merges
it into ``BENCH_serve.json`` at the repo root (``make bench-json`` runs
every mode), so the perf trajectory is tracked across PRs —
``tools/bench_table.py`` regenerates the README benchmark table from that
file and ``tools/docs_check.py`` fails the build when quoted numbers go
stale.

  PYTHONPATH=src python benchmarks/serve_bench.py --arch granite-3-2b \
      --requests 16 --slots 4 --gap 2.0 --new-tokens 8
  PYTHONPATH=src python benchmarks/serve_bench.py --shared-prefix \
      --requests 8 --prefill-chunk 4
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import time
from typing import Any

import jax

from repro import configs
from repro.models import registry
from repro.serve.engine import ContinuousBatchingEngine, Request
from repro.serve.sim import (FakeClock, Simulator, shared_prefix_requests,
                             staggered_trace)
from repro.sharding import params as P

REPO = pathlib.Path(__file__).resolve().parent.parent
RESULTS = REPO / "results" / "serve"
BENCH_JSON = REPO / "BENCH_serve.json"


def build_requests(n: int, prompt_len: int, new_tokens: int) -> list[Request]:
    return [
        Request(id=f"req{i}",
                prompt=[(11 * i + j) % 241 + 1 for j in range(prompt_len)],
                max_new_tokens=new_tokens)
        for i in range(n)
    ]


def run_once(cfg, params, args, *, mode: str, sequential: bool = False,
             requests=None, max_len=None, **engine_kwargs) -> tuple[dict, Any]:
    clock = FakeClock()
    eng = ContinuousBatchingEngine(cfg, params, slots=args.slots,
                                   max_len=max_len or args.max_len,
                                   clock=clock,
                                   prefill_chunk=args.prefill_chunk,
                                   **engine_kwargs)
    if requests is None:
        requests = build_requests(args.requests, args.prompt_len,
                                  args.new_tokens)
    trace = staggered_trace(requests, gap=args.gap)
    sim = Simulator(eng, trace, clock, step_time=args.step_time,
                    dispatch_time=args.dispatch_time, sequential=sequential)
    w0 = time.perf_counter()
    report = sim.run()
    wall = time.perf_counter() - w0
    lat = [r.finish_time - r.arrival_time for r in report.completed]
    return {
        "mode": mode,
        "backend": eng.stats()["backend"],
        "async_dispatch": eng.async_dispatch,
        "elapsed_sim": report.elapsed,
        "engine_steps": report.steps,
        "tokens": report.tokens_generated,
        "throughput_tok_per_sim_s": round(report.throughput, 4),
        "mean_latency_sim": round(sum(lat) / len(lat), 3),
        # nearest-rank p99: for n <= 100 this is the max (the tail straggler
        # must be visible, not floored away)
        "p99_latency_sim": round(
            sorted(lat)[max(0, math.ceil(0.99 * len(lat)) - 1)], 3),
        "wall_s": round(wall, 3),
        "wall_tok_per_s": round(report.tokens_generated / wall, 1),
    }, eng


def _tokens(eng) -> dict:
    return {r.id: tuple(r.tokens) for r in eng.completed}


def _assert_identical(named_engines) -> None:
    """The perf claim is only valid if the outputs are the same outputs."""
    (base_name, base), *rest = named_engines
    want = _tokens(base)
    for name, eng in rest:
        got = _tokens(eng)
        if got != want:
            raise AssertionError(
                f"outputs diverged: {name} != {base_name} — the engines "
                f"must be bit-identical before throughput is comparable")


def _print_mode(mode: dict) -> None:
    tag = "async" if mode["async_dispatch"] else "sync"
    print(f"{mode['mode']:>12} [{mode['backend']}/{tag}]: "
          f"{mode['tokens']} tokens in {mode['elapsed_sim']:.1f} sim-s "
          f"({mode['throughput_tok_per_sim_s']:.3f} tok/sim-s), "
          f"mean latency {mode['mean_latency_sim']:.2f} sim-s, "
          f"wall {mode['wall_s']:.2f}s")


def run_default(cfg, params, args) -> tuple[dict, float]:
    """New engine (paged + async double-buffered dispatch) vs the PR 2
    engine (cache lanes, synchronous) vs sequential, same trace."""
    new, eng_new = run_once(cfg, params, args, mode="async-paged",
                            async_dispatch=True)
    pr2, eng_pr2 = run_once(cfg, params, args, mode="pr2-sync", paged=False)
    seq, eng_seq = run_once(cfg, params, args, mode="sequential",
                            paged=False, sequential=True)
    _assert_identical([("pr2-sync", eng_pr2), ("async-paged", eng_new),
                       ("sequential", eng_seq)])
    async_speedup = (new["throughput_tok_per_sim_s"]
                     / pr2["throughput_tok_per_sim_s"])
    seq_speedup = (new["throughput_tok_per_sim_s"]
                   / seq["throughput_tok_per_sim_s"])
    out = {"arch": cfg.name, "requests": args.requests, "slots": args.slots,
           "gap": args.gap, "dispatch_time": args.dispatch_time,
           "step_time": args.step_time,
           "async_paged": new, "pr2_sync": pr2, "sequential": seq,
           "async_speedup_vs_pr2": round(async_speedup, 3),
           "speedup_vs_sequential": round(seq_speedup, 3)}
    if not args.json:
        for mode in (new, pr2, seq):
            _print_mode(mode)
        print(f"async paged dispatch vs PR 2 engine: {async_speedup:.2f}x "
              f"(vs sequential: {seq_speedup:.2f}x); outputs bit-identical")
    return out, async_speedup


def run_shared_prefix(cfg, params, args) -> tuple[dict, float]:
    """Same shared-prefix trace with paged sharing on/off and through the
    PR 2 sharing engine; the speedups isolate page reuse and async+paged."""
    prefix_len = args.shared_prefix
    make = lambda: shared_prefix_requests(
        args.requests, prefix_len=prefix_len, tail_len=args.tail_len,
        new_tokens=args.new_tokens)
    need = prefix_len + args.tail_len + args.new_tokens + 1
    max_len = max(args.max_len, need)
    shared, eng = run_once(cfg, params, args, mode="sharing",
                           requests=make(), max_len=max_len,
                           page_size=args.page_size, async_dispatch=True)
    plain, eng_plain = run_once(cfg, params, args, mode="no-sharing",
                                requests=make(), max_len=max_len,
                                async_dispatch=True)
    pr2, eng_pr2 = run_once(cfg, params, args, mode="pr2-sharing",
                            requests=make(), max_len=max_len,
                            page_size=args.page_size, paged=False)
    _assert_identical([("pr2-sharing", eng_pr2), ("sharing", eng),
                       ("no-sharing", eng_plain)])
    sharing_speedup = (shared["throughput_tok_per_sim_s"]
                       / plain["throughput_tok_per_sim_s"])
    vs_pr2 = (shared["throughput_tok_per_sim_s"]
              / pr2["throughput_tok_per_sim_s"])
    stats = eng.stats()
    pages = stats["pages"]
    out = {"arch": cfg.name, "requests": args.requests, "slots": args.slots,
           "gap": args.gap, "shared_prefix": prefix_len,
           "page_size": args.page_size, "prefill_chunk": args.prefill_chunk,
           "dispatch_time": args.dispatch_time, "step_time": args.step_time,
           "sharing": shared, "no_sharing": plain, "pr2_sharing": pr2,
           "pages": pages, "pool": stats.get("pool"),
           "stalls": stats["stalls"], "rematches": stats["rematches"],
           "sharing_speedup": round(sharing_speedup, 3),
           "async_speedup_vs_pr2": round(vs_pr2, 3)}
    if not args.json:
        for mode in (shared, plain, pr2):
            _print_mode(mode)
        print(f"pages: {pages['hits']} hits / {pages['misses']} misses, "
              f"{pages['tokens_reused']} prompt tokens reused, "
              f"{stats['rematches']} mid-flight re-matches, "
              f"{stats['stalls']} dedup stalls, "
              f"{pages['resident']} resident")
        print(f"prefix sharing speedup: {sharing_speedup:.2f}x; "
              f"vs PR 2 sharing engine: {vs_pr2:.2f}x; outputs bit-identical")
    return out, vs_pr2


def run_multi_model(args) -> tuple[dict, float]:
    """Multi-model cluster vs the same engines isolated.

    Three engines, two models: ``rep-a``/``rep-b`` serve ``--arch`` as
    replicas under one namespace (their shared-prefix traffic aliases
    *across* engines on the cluster), ``alt`` serves ``--arch-b`` in its
    own namespace (isolated prefixes, shared pool budget). ``rep-b`` is an
    elastic scale-out replica: its traffic starts after ``rep-a`` has
    absorbed the first wave — on the cluster it finds the shared prefix
    pages already resident (admitted pre-consumed, zero prefill for the
    hot prefix), while the isolated baseline pays the cold prefill again.
    The isolated baseline runs each engine on its own pool/table and own
    clock; since isolated engines run concurrently in real deployments,
    its aggregate throughput is total tokens over the slowest engine's
    span.
    """
    from repro.serve.cluster import ServeCluster
    from repro.serve.sim import ClusterSimulator, tag_engine

    cfg_a = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    cfg_b = (configs.smoke(args.arch_b) if args.smoke
             else configs.get(args.arch_b))
    params_a = P.init_tree(registry.decls(cfg_a), jax.random.key(args.seed))
    params_b = P.init_tree(registry.decls(cfg_b),
                           jax.random.key(args.seed + 1))

    n = max(2, args.requests // 2)
    prefix_len, ps = args.shared_prefix or 16, args.page_size
    need = prefix_len + args.tail_len + args.new_tokens + 1
    max_len = max(args.max_len, need)
    alt_prefix = [(19 * j) % 239 + 2 for j in range(prefix_len)]
    make = {
        "rep-a": lambda: shared_prefix_requests(
            n, prefix_len=prefix_len, tail_len=args.tail_len,
            new_tokens=args.new_tokens, id_prefix="ga"),
        "rep-b": lambda: shared_prefix_requests(
            n, prefix_len=prefix_len, tail_len=args.tail_len,
            new_tokens=args.new_tokens, id_prefix="gb"),
        "alt": lambda: shared_prefix_requests(
            n, prefix_len=prefix_len, tail_len=args.tail_len,
            new_tokens=args.new_tokens, prefix=alt_prefix, id_prefix="sl"),
    }
    members = [("rep-a", cfg_a, params_a, cfg_a.name),
               ("rep-b", cfg_a, params_a, cfg_a.name),
               ("alt", cfg_b, params_b, cfg_b.name)]
    np_max = -(-max_len // ps)
    pool_pages = 3 * args.slots * np_max + 16
    # rep-b scales out mid-run: its trace starts once rep-a's first wave
    # is underway, so the shared prefix is resident on the cluster
    starts = {"rep-a": 0.0, "rep-b": n * args.gap, "alt": 0.0}

    def isolated(name, cfg, params):
        clock = FakeClock()
        eng = ContinuousBatchingEngine(
            cfg, params, slots=args.slots, max_len=max_len, clock=clock,
            prefill_chunk=args.prefill_chunk, page_size=ps)
        sim = Simulator(eng, staggered_trace(make[name](), gap=args.gap,
                                             start=starts[name]),
                        clock, step_time=args.step_time,
                        dispatch_time=args.dispatch_time)
        return eng, sim.run()

    w0 = time.perf_counter()
    iso = {name: isolated(name, cfg, params)
           for name, cfg, params, _ in members}
    iso_wall = time.perf_counter() - w0

    clock = FakeClock()
    cluster = ServeCluster(pool_pages=pool_pages, page_size=ps, clock=clock)
    for name, cfg, params, ns in members:
        cluster.add_engine(cfg, params, name=name, namespace=ns,
                           slots=args.slots, max_len=max_len,
                           prefill_chunk=args.prefill_chunk)
    trace = [a for name, _, _, _ in members
             for a in tag_engine(staggered_trace(make[name](), gap=args.gap,
                                                 start=starts[name]), name)]
    w0 = time.perf_counter()
    rep = ClusterSimulator(cluster, trace, clock, step_time=args.step_time,
                           dispatch_time=args.dispatch_time).run()
    wall = time.perf_counter() - w0

    # the perf claim is only valid if the outputs are the same outputs
    for name, _, _, _ in members:
        _assert_identical([(f"isolated:{name}", iso[name][0]),
                           (f"cluster:{name}", cluster.engines[name])])

    iso_tokens = sum(r.tokens_generated for _, r in iso.values())
    iso_elapsed = max(r.elapsed for _, r in iso.values())
    iso_tp = iso_tokens / iso_elapsed
    speedup = rep.throughput / iso_tp
    engines = {name: {
        "arch": eng.cfg.name,
        "namespace": eng.namespace,
        "prompt_tokens_reused": eng.prompt_tokens_reused,
        "prompt_tokens_processed": eng.prompt_tokens_processed,
        "rematches": eng.rematches,
    } for name, eng in cluster.engines.items()}
    cstats = cluster.stats()
    out = {"arch": cfg_a.name, "arch_b": cfg_b.name,
           "requests_per_engine": n, "slots": args.slots, "gap": args.gap,
           "shared_prefix": prefix_len, "page_size": ps,
           "prefill_chunk": args.prefill_chunk,
           "dispatch_time": args.dispatch_time, "step_time": args.step_time,
           "cluster": {
               "elapsed_sim": rep.elapsed, "steps": rep.steps,
               "tokens": rep.tokens_generated,
               "throughput_tok_per_sim_s": round(rep.throughput, 4),
               "wall_s": round(wall, 3),
               "pool_pages": pool_pages,
               "pool_device_pages": cluster.pool.device_pages,
               "pool_high_water": cstats["pool"]["high_water"],
               "table_resident_by_ns": cstats["table"]["by_namespace"],
               "engines": engines,
           },
           "isolated": {
               "elapsed_sim": iso_elapsed, "tokens": iso_tokens,
               "throughput_tok_per_sim_s": round(iso_tp, 4),
               "wall_s": round(iso_wall, 3),
               "pool_pages_total": sum(e._pool.n_pages
                                       for e, _ in iso.values()),
               "pool_device_pages_total": sum(e._pool.device_pages
                                              for e, _ in iso.values()),
               "pool_high_water_total": sum(e._pool.stats["high_water"]
                                            for e, _ in iso.values()),
           },
           "cluster_speedup_vs_isolated": round(speedup, 3)}
    if not args.json:
        print(f"cluster [3 engines, 2 models, one {pool_pages}-id pool, "
              f"{cluster.pool.device_pages} device pages across "
              f"{len(cluster.pool._arenas)} arenas]: "
              f"{rep.tokens_generated} tokens in {rep.elapsed:.1f} "
              f"sim-s ({rep.throughput:.3f} tok/sim-s), pool high-water "
              f"{cstats['pool']['high_water']}")
        print(f"isolated [3 engines, private pools, "
              f"{out['isolated']['pool_device_pages_total']} device pages "
              f"total]: {iso_tokens} tokens in {iso_elapsed:.1f} sim-s "
              f"({iso_tp:.3f} tok/sim-s), pool high-water "
              f"{out['isolated']['pool_high_water_total']}")
        for name, st in engines.items():
            print(f"  {name} [{st['arch']} ns={st['namespace']}]: "
                  f"{st['prompt_tokens_reused']} prompt tokens reused")
        print(f"cluster vs isolated: {speedup:.2f}x aggregate tokens/s; "
              f"outputs bit-identical per engine")
    return out, speedup


def run_sliding_window(args) -> tuple[dict, float]:
    """Sliding-window serving on the paged backend vs the lane ring cache.

    The config is ``--arch``'s smoke model with ``sliding_window`` set to
    the flag's value; prompts and generations run well past the window so
    every slot recycles ring pages. Three engines on the same trace:
    the windowed paged engine with async dispatch (the new path), the same
    backend synchronous, and the lane ring cache (the fallback this PR
    retires) — bit-identity asserted across all three before any number
    is reported. The memory claim is structural: a windowed slot's block
    table has ``ceil(window/page_size) + 1`` entries, so the engine
    provisions O(window) pool pages per slot instead of O(max_len).
    """
    base = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    w = args.sliding_window
    cfg = dataclasses.replace(base, name=f"{base.name}-swa{w}",
                              sliding_window=w)
    params = P.init_tree(registry.decls(cfg), jax.random.key(args.seed))
    prompt_len = max(args.prompt_len, w + args.page_size)
    max_len = max(args.max_len, prompt_len + args.new_tokens + 1)
    make = lambda: build_requests(args.requests, prompt_len, args.new_tokens)

    paged, eng = run_once(cfg, params, args, mode="swa-paged-async",
                          requests=make(), max_len=max_len,
                          page_size=args.page_size, async_dispatch=True)
    sync, eng_sync = run_once(cfg, params, args, mode="swa-paged-sync",
                              requests=make(), max_len=max_len,
                              page_size=args.page_size)
    lanes, eng_lanes = run_once(cfg, params, args, mode="swa-lane-ring",
                                requests=make(), max_len=max_len,
                                page_size=args.page_size, paged=False)
    _assert_identical([("swa-lane-ring", eng_lanes),
                       ("swa-paged-sync", eng_sync),
                       ("swa-paged-async", eng)])
    assert eng.stats()["backend"] == "paged", "SWA must run the paged backend"
    speedup = (paged["throughput_tok_per_sim_s"]
               / lanes["throughput_tok_per_sim_s"])
    stats = eng.stats()
    np_unwindowed = -(-max_len // args.page_size)
    out = {"arch": cfg.name, "window": w, "requests": args.requests,
           "slots": args.slots, "gap": args.gap, "prompt_len": prompt_len,
           "new_tokens": args.new_tokens, "max_len": max_len,
           "page_size": args.page_size, "prefill_chunk": args.prefill_chunk,
           "dispatch_time": args.dispatch_time, "step_time": args.step_time,
           "paged_async": paged, "paged_sync": sync, "lane_ring": lanes,
           "table_entries_per_slot": stats["table_entries_per_slot"],
           "unwindowed_pages_per_slot": np_unwindowed,
           "pages_recycled": stats["pages_recycled"],
           "pool": stats["pool"],
           "paged_speedup_vs_lane_ring": round(speedup, 3)}
    if not args.json:
        for mode in (paged, sync, lanes):
            _print_mode(mode)
        print(f"ring block tables: {stats['table_entries_per_slot']} "
              f"entries/slot (window {w} / page {args.page_size}) vs "
              f"{np_unwindowed} unwindowed; pool "
              f"{stats['pool']['pages']} pages, high-water "
              f"{stats['pool']['high_water']}, "
              f"{stats['pages_recycled']} pages recycled")
        print(f"windowed paged (async) vs lane ring cache: {speedup:.2f}x "
              f"tokens/s; outputs bit-identical")
    return out, speedup


def run_open_loop(args) -> tuple[dict, float]:
    """Open-loop traffic at 10⁵-request scale: SLO-aware vs flat WRR.

    A lazily generated bursty arrival trace (``repro.serve.loadgen``) is
    driven through a 3-engine cluster at an offered load far above
    capacity — arrivals never wait for the system, so queues build,
    backpressure rejects, and the question becomes *goodput*: tokens
    delivered inside each request's SLO. Two scheduling policies serve
    the byte-identical trace:

    * ``slo_sched`` — deficit-weighted round-robin over ``step_cost()``
      plus latency-SLO admission control (shed queue heads that already
      blew their TTFT budget) plus preempt-and-requeue of decoding
      requests past their deadline.
    * ``flat_wrr`` — the PR 4 scheduler: fixed grants, FIFO heads, no
      shedding. Under overload it serves a stale backlog, so most of its
      completions bust their TTFT target.

    Determinism is asserted, not assumed: the SLO run executes twice from
    two independently constructed clusters and generators, and the
    reports, metric summaries, and every request's token stream must be
    bit-identical. Requests completed by both policies must also produce
    identical tokens (scheduling may reorder work, never change it).
    """
    from repro.serve.cluster import SchedPolicy, ServeCluster
    from repro.serve.loadgen import TenantSpec, open_loop_trace
    from repro.serve.metrics import SLO, ServeMetrics
    from repro.serve.sim import ClusterSimulator

    n, rate = args.open_loop, args.open_loop_rate
    cfg_a = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    cfg_b = (configs.smoke(args.arch_b) if args.smoke
             else configs.get(args.arch_b))
    params_a = P.init_tree(registry.decls(cfg_a), jax.random.key(args.seed))
    params_b = P.init_tree(registry.decls(cfg_b),
                           jax.random.key(args.seed + 1))

    ttft_cap, tpot_rep, tpot_alt = 25.0, 4.0, 1.0
    # two replicas of one model (shared namespace + prefix_seed: their
    # bursts exercise cross-engine cold-prefill dedup) and one long-output
    # tenant whose tight per-token budget makes its tails preemptable
    tenants = [
        TenantSpec(engine="rep-a", share=1.0, prompt_len=(6, 18),
                   new_tokens=(4, 10), prefix_len=8, prefix_seed=7,
                   slo=SLO(ttft=ttft_cap, tpot=tpot_rep)),
        TenantSpec(engine="rep-b", share=1.0, prompt_len=(6, 18),
                   new_tokens=(4, 10), prefix_len=8, prefix_seed=7,
                   slo=SLO(ttft=ttft_cap, tpot=tpot_rep)),
        TenantSpec(engine="alt", share=0.5, prompt_len=(4, 12),
                   new_tokens=(16, 28), prefix_len=6, prefix_seed=3,
                   slo=SLO(ttft=ttft_cap, tpot=tpot_alt)),
    ]
    max_len = {"rep-a": 32, "rep-b": 32, "alt": 48}
    ps = 8
    pool_pages = sum(args.slots * -(-m // ps) for m in max_len.values()) + 24

    def drive(policy):
        clock = FakeClock()
        cluster = ServeCluster(pool_pages=pool_pages, page_size=ps,
                               clock=clock, policy=policy)
        for name, cfg, params, ns in (
                ("rep-a", cfg_a, params_a, cfg_a.name),
                ("rep-b", cfg_a, params_a, cfg_a.name),
                ("alt", cfg_b, params_b, cfg_b.name)):
            cluster.add_engine(cfg, params, name=name, namespace=ns,
                               slots=args.slots, max_len=max_len[name],
                               prefill_chunk=args.prefill_chunk,
                               queue_capacity=args.queue_capacity)
        trace = open_loop_trace(tenants, n_requests=n, rate=rate,
                                seed=args.seed,
                                process=args.open_loop_process)
        sim = ClusterSimulator(cluster, trace, clock,
                               step_time=args.step_time,
                               dispatch_time=args.dispatch_time)
        w0 = time.perf_counter()
        report = sim.run(max_steps=5_000_000)
        wall = time.perf_counter() - w0
        metrics = ServeMetrics()
        tokens = {}
        for eng in cluster.engines.values():
            metrics.observe_all(eng.completed)
            tokens.update((r.id, tuple(r.tokens)) for r in eng.completed)
        return report, metrics.summary(elapsed=report.elapsed), tokens, \
            cluster, wall

    def digest(report, summary, tokens):
        return (report.elapsed, report.steps, report.tokens_generated,
                report.rejected, report.shed,
                {k: [r.id for r in v] for k, v in report.completed.items()},
                summary, tokens)

    slo_policy = SchedPolicy(scheduler="drr", shed_busted=True,
                             preempt_busted=True)
    rep1, sum1, tok1, cl1, wall1 = drive(slo_policy)
    rep2, sum2, tok2, cl2, _ = drive(slo_policy)
    if digest(rep1, sum1, tok1) != digest(rep2, sum2, tok2):
        raise AssertionError(
            "open-loop run is not deterministic: two same-seed runs "
            "diverged — the trace/scheduler must be bit-reproducible")
    compare = not args.open_loop_skip_flat
    if compare:
        flat, sumf, tokf, clf, wallf = drive(SchedPolicy())
        common = tok1.keys() & tokf.keys()
        diverged = [i for i in common if tok1[i] != tokf[i]]
        if diverged:
            raise AssertionError(
                f"{len(diverged)} requests produced different tokens under "
                "the two schedulers (e.g. "
                f"{sorted(diverged)[:3]}) — scheduling must never change "
                "outputs")
        gain = (sum1["goodput"] / sumf["goodput"]
                if sumf.get("goodput") else float("inf"))
    else:
        gain = 1.0

    def mode(tag, report, summary, cluster, wall):
        return {
            "policy": tag, "elapsed_sim": report.elapsed,
            "rounds": report.steps, "tokens": report.tokens_generated,
            "served": summary["completed"], "rejected": report.rejected,
            "shed": report.shed, "slo_preempts": cluster.slo_preempts,
            "ttft_p50": round(summary["ttft_p50"], 3),
            "ttft_p99": round(summary["ttft_p99"], 3),
            "tpot_p50": round(summary["tpot_p50"], 3),
            "tpot_p99": round(summary["tpot_p99"], 3),
            "slo_attainment": round(summary["slo_attainment"], 4),
            "goodput_tok_per_sim_s": round(summary["goodput"], 4),
            "throughput_tok_per_sim_s": round(report.throughput, 4),
            "wall_s": round(wall, 3),
        }

    out = {"arch": cfg_a.name, "arch_b": cfg_b.name, "requests": n,
           "rate": rate, "process": args.open_loop_process, "engines": 3,
           "slots": args.slots, "queue_capacity": args.queue_capacity,
           "page_size": ps, "prefill_chunk": args.prefill_chunk,
           "dispatch_time": args.dispatch_time, "step_time": args.step_time,
           "slo": {"ttft": ttft_cap, "tpot_rep": tpot_rep,
                   "tpot_alt": tpot_alt},
           "slo_sched": mode("drr+shed+preempt", rep1, sum1, cl1, wall1),
           "deterministic": True}
    if compare:
        out["flat_wrr"] = mode("wrr", flat, sumf, clf, wallf)
        out["goodput_gain"] = round(gain, 3)
    if n >= 10_000:
        # at bench scale the SLO machinery must demonstrably engage and win
        assert rep1.shed > 0, "no SLO-busted heads were shed"
        assert cl1.slo_preempts > 0, "no SLO-busting tails were preempted"
        if compare:
            assert gain > 1.0, (
                f"SLO-aware scheduling must beat flat WRR on goodput "
                f"(got {gain:.3f}x)")
    if not args.json:
        for m in ([out["slo_sched"], out["flat_wrr"]] if compare
                  else [out["slo_sched"]]):
            print(f"{m['policy']:>16}: {m['served']} served / "
                  f"{m['rejected']} rejected / {m['shed']} shed of {n} "
                  f"arrivals in {m['elapsed_sim']:.0f} sim-s; TTFT p50/p99 "
                  f"{m['ttft_p50']:.1f}/{m['ttft_p99']:.1f}, TPOT p99 "
                  f"{m['tpot_p99']:.2f}, attainment "
                  f"{m['slo_attainment']:.1%}, goodput "
                  f"{m['goodput_tok_per_sim_s']:.3f} tok/sim-s")
        if compare:
            print(f"SLO-aware vs flat WRR goodput: {gain:.2f}x; two "
                  f"same-seed runs bit-identical ({n} open-loop arrivals)")
        else:
            print(f"two same-seed runs bit-identical ({n} open-loop "
                  f"arrivals; flat-WRR comparison skipped)")
    return out, gain


def run_sampling(args) -> tuple[dict, float]:
    """Deterministic stochastic sampling at open-loop scale.

    One engine under the full SLO-aware cluster policy serves a bursty
    open-loop mix of three tenants — one hot (temperature + top-k +
    top-p), one nucleus-only, one greedy control — at an offered load
    far above capacity, so the run exercises sampling through queue
    buildup, shedding, and SLO preempt-and-requeue. Three drives over the
    *byte-identical* arrival sequence (materialised once, fresh Request
    objects per drive):

    * sampled, twice: the per-request journaled PRNG chains must make the
      two runs bit-identical — reports, metric summaries, every token.
    * greedy (the same requests with ``sampling`` stripped): the sampled
      tenants' streams must actually diverge from greedy decode, and the
      greedy control tenant's streams must be bit-identical across the
      sampled and stripped drives (a neighbour's PRNG never leaks).

    Run with ``--open-loop-rate 40`` (the ``make bench-json`` line): still
    far above the three engines' capacity, but admitting enough of the
    tight-TPOT tenant's long decodes that deadline preempt-and-requeue
    demonstrably engages — at rate 100 nearly everything is rejected at
    the queue and nothing lives long enough to be demoted.
    """
    from repro.serve.cluster import SchedPolicy, ServeCluster
    from repro.serve.loadgen import TenantSpec, open_loop_trace
    from repro.serve.metrics import SLO, ServeMetrics
    from repro.serve.sampling import SamplingParams
    from repro.serve.sim import Arrival, ClusterSimulator

    n, rate = args.sampling, args.open_loop_rate
    cfg_a = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    cfg_b = (configs.smoke(args.arch_b) if args.smoke
             else configs.get(args.arch_b))
    params_a = P.init_tree(registry.decls(cfg_a), jax.random.key(args.seed))
    params_b = P.init_tree(registry.decls(cfg_b),
                           jax.random.key(args.seed + 1))

    # the run_open_loop topology — two replicas plus a preemptable
    # long-output tenant — with sampling attached: hot sampling on rep-a,
    # greedy control on rep-b (same model, same namespace), nucleus
    # sampling on the tight-TPOT tenant whose tails get demoted
    hot = SamplingParams(temperature=0.8, top_k=40, top_p=0.95)
    nucleus = SamplingParams(temperature=1.0, top_p=0.9)
    tenants = [
        TenantSpec(engine="rep-a", share=1.0, prompt_len=(6, 18),
                   new_tokens=(4, 10), prefix_len=8, prefix_seed=7,
                   slo=SLO(ttft=25.0, tpot=4.0), sampling=hot),
        TenantSpec(engine="rep-b", share=1.0, prompt_len=(6, 18),
                   new_tokens=(4, 10), prefix_len=8, prefix_seed=7,
                   slo=SLO(ttft=25.0, tpot=4.0)),
        TenantSpec(engine="alt", share=0.5, prompt_len=(4, 12),
                   new_tokens=(16, 28), slo=SLO(ttft=25.0, tpot=1.0),
                   sampling=nucleus),
    ]
    max_len = {"rep-a": 32, "rep-b": 32, "alt": 48}
    ps = 8
    pool_pages = sum(args.slots * -(-m // ps) for m in max_len.values()) + 24
    # materialise the arrival sequence once; every drive rebuilds fresh
    # Request objects from it (requests are engine-mutated, and the greedy
    # drive must see the very same prompts with only `sampling` stripped)
    base = [(a.time, a.request.id, tuple(a.request.prompt),
             a.request.max_new_tokens, a.request.slo, a.request.sampling,
             a.engine)
            for a in open_loop_trace(tenants, n_requests=n, rate=rate,
                                     seed=args.seed,
                                     process=args.open_loop_process)]
    sampled_ids = {rid for _, rid, _, _, _, sp, _ in base if sp is not None}

    def drive(strip):
        clock = FakeClock()
        cluster = ServeCluster(pool_pages=pool_pages, page_size=ps,
                               clock=clock,
                               policy=SchedPolicy(scheduler="drr",
                                                  shed_busted=True,
                                                  preempt_busted=True))
        for name, cfg, params, ns in (
                ("rep-a", cfg_a, params_a, cfg_a.name),
                ("rep-b", cfg_a, params_a, cfg_a.name),
                ("alt", cfg_b, params_b, cfg_b.name)):
            cluster.add_engine(cfg, params, name=name, namespace=ns,
                               slots=args.slots, max_len=max_len[name],
                               prefill_chunk=args.prefill_chunk,
                               queue_capacity=args.queue_capacity)
        trace = (Arrival(t, Request(id=rid, prompt=list(p),
                                    max_new_tokens=m, slo=slo,
                                    sampling=None if strip else sp), e)
                 for t, rid, p, m, slo, sp, e in base)
        sim = ClusterSimulator(cluster, trace, clock,
                               step_time=args.step_time,
                               dispatch_time=args.dispatch_time)
        w0 = time.perf_counter()
        report = sim.run(max_steps=5_000_000)
        wall = time.perf_counter() - w0
        metrics = ServeMetrics()
        tokens, sampled_served = {}, 0
        for eng in cluster.engines.values():
            metrics.observe_all(eng.completed)
            tokens.update((r.id, tuple(r.tokens)) for r in eng.completed)
            sampled_served += eng.sampled_requests
        return (report, metrics.summary(elapsed=report.elapsed),
                tokens, cluster, sampled_served, wall)

    def digest(report, summary, tokens):
        return (report.elapsed, report.steps, report.tokens_generated,
                report.rejected, report.shed, summary, tokens)

    rep1, sum1, tok1, cl1, samp1, wall1 = drive(strip=False)
    rep2, sum2, tok2, _, _, _ = drive(strip=False)
    if digest(rep1, sum1, tok1) != digest(rep2, sum2, tok2):
        raise AssertionError(
            "sampled open-loop run is not deterministic: two same-seed "
            "runs diverged — the journaled per-request PRNG chains must "
            "make sampling bit-reproducible")
    repg, sumg, tokg, clg, sampg, wallg = drive(strip=True)

    common = tok1.keys() & tokg.keys()
    greedy_ctl = [i for i in common if i not in sampled_ids]
    leaked = [i for i in greedy_ctl if tok1[i] != tokg[i]]
    if leaked:
        raise AssertionError(
            f"{len(leaked)} greedy-tenant requests changed tokens when "
            f"their neighbours sampled (e.g. {sorted(leaked)[:3]}) — "
            "per-lane PRNG state must not leak across slots")
    sampled_common = [i for i in common if i in sampled_ids]
    diverged = [i for i in sampled_common if tok1[i] != tokg[i]]
    frac = len(diverged) / len(sampled_common) if sampled_common else 0.0
    if sampled_common and frac <= 0.5:
        raise AssertionError(
            f"only {len(diverged)}/{len(sampled_common)} sampled requests "
            "diverged from greedy decode — sampling is not actually "
            "engaging")

    def mode(tag, report, summary, cluster, sampled_served, wall):
        return {
            "mode": tag, "elapsed_sim": report.elapsed,
            "tokens": report.tokens_generated,
            "served": summary["completed"], "rejected": report.rejected,
            "shed": report.shed, "slo_preempts": cluster.slo_preempts,
            "sampled_requests": sampled_served,
            "slo_attainment": round(summary["slo_attainment"], 4),
            "goodput_tok_per_sim_s": round(summary["goodput"], 4),
            "throughput_tok_per_sim_s": round(report.throughput, 4),
            "wall_s": round(wall, 3),
        }

    out = {"arch": cfg_a.name, "arch_b": cfg_b.name, "requests": n,
           "rate": rate, "process": args.open_loop_process, "engines": 3,
           "slots": args.slots,
           "queue_capacity": args.queue_capacity, "page_size": ps,
           "prefill_chunk": args.prefill_chunk,
           "dispatch_time": args.dispatch_time, "step_time": args.step_time,
           "tenants": {"hot": {"temperature": hot.temperature,
                               "top_k": hot.top_k, "top_p": hot.top_p},
                       "nucleus": {"temperature": nucleus.temperature,
                                   "top_p": nucleus.top_p},
                       "greedy_share": 0.5},
           "sampled": mode("sampled", rep1, sum1, cl1, samp1, wall1),
           "greedy": mode("greedy", repg, sumg, clg, sampg, wallg),
           "divergence": {
               "common_served": len(common),
               "sampled_common": len(sampled_common),
               "diverged_vs_greedy": len(diverged),
               "diverged_frac": round(frac, 4),
               "greedy_tenant_identical": True,
           },
           "deterministic": True}
    if n >= 2_000 and rate <= 50.0:
        # at bench scale the replay machinery must demonstrably engage
        assert cl1.slo_preempts > 0, "no sampled decode was SLO-preempted"
        assert rep1.shed > 0, "no SLO-busted heads were shed"
    if not args.json:
        for m in (out["sampled"], out["greedy"]):
            print(f"{m['mode']:>8}: {m['served']} served / "
                  f"{m['rejected']} rejected / {m['shed']} shed of {n}; "
                  f"{m['tokens']} tokens in {m['elapsed_sim']:.0f} sim-s, "
                  f"{m['slo_preempts']} SLO preempts, "
                  f"{m['sampled_requests']} sampled admissions")
        print(f"two same-seed sampled runs bit-identical; "
              f"{len(diverged)}/{len(sampled_common)} sampled streams "
              f"diverged from greedy ({frac:.1%}); greedy tenant untouched")
    return out, frac


def run_chaos(args) -> tuple[dict, float]:
    """Chaos-tolerant serving at open-loop scale.

    The ``run_sampling`` topology (three engines, hot/nucleus/greedy
    tenants, full SLO-aware policy) is driven three times over the
    byte-identical arrival sequence:

    * fault-free — the reference run;
    * chaos — a seeded :class:`~repro.serve.chaos.FaultPlan` injects
      device-step failures, corrupted tokens, NaN logits, page-allocation
      failures, engine crashes, bank power-faults, and prefix-match drops
      while the cluster recovers (retry-with-backoff, corruption
      quarantine + journal replay, watchdog-gated crash rebuild);
    * chaos again, same seed — the determinism control.

    Built-in assertions (the tentpole invariant): every request completed
    by both the fault-free and the chaos run has bit-identical tokens;
    within each run every submitted request is accounted exactly once
    (completed + shed + rejected, no duplicates); and the two same-seed
    chaos runs are bit-identical end to end, fault schedule included.
    Reported: injections by kind, recovery counters (retries, replays,
    rebuilds), recovery overhead (extra sim-time under faults), and
    goodput retention (chaos goodput / fault-free goodput).
    """
    from repro.runtime.ft import FTConfig
    from repro.serve.chaos import FaultPlan, FaultSpec
    from repro.serve.cluster import SchedPolicy, ServeCluster
    from repro.serve.loadgen import TenantSpec, open_loop_trace
    from repro.serve.metrics import SLO, ServeMetrics
    from repro.serve.sampling import SamplingParams
    from repro.serve.sim import Arrival, ClusterSimulator

    n, rate = args.chaos, args.open_loop_rate
    cfg_a = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    cfg_b = (configs.smoke(args.arch_b) if args.smoke
             else configs.get(args.arch_b))
    params_a = P.init_tree(registry.decls(cfg_a), jax.random.key(args.seed))
    params_b = P.init_tree(registry.decls(cfg_b),
                           jax.random.key(args.seed + 1))

    hot = SamplingParams(temperature=0.8, top_k=40, top_p=0.95)
    tenants = [
        TenantSpec(engine="rep-a", share=1.0, prompt_len=(6, 18),
                   new_tokens=(4, 10), prefix_len=8, prefix_seed=7,
                   slo=SLO(ttft=25.0, tpot=4.0), sampling=hot),
        TenantSpec(engine="rep-b", share=1.0, prompt_len=(6, 18),
                   new_tokens=(4, 10), prefix_len=8, prefix_seed=7,
                   slo=SLO(ttft=25.0, tpot=4.0)),
        TenantSpec(engine="alt", share=0.5, prompt_len=(4, 12),
                   new_tokens=(16, 28), slo=SLO(ttft=25.0, tpot=1.0)),
    ]
    max_len = {"rep-a": 32, "rep-b": 32, "alt": 48}
    ps = 8
    pool_pages = sum(args.slots * -(-m // ps) for m in max_len.values()) + 24
    base = [(a.time, a.request.id, tuple(a.request.prompt),
             a.request.max_new_tokens, a.request.slo, a.request.sampling,
             a.engine)
            for a in open_loop_trace(tenants, n_requests=n, rate=rate,
                                     seed=args.seed,
                                     process=args.open_loop_process)]
    # modest per-point rates; crashes are budgeted (each one costs an
    # exponentially growing restart backoff, so an unbounded crash count
    # would spend the whole run waiting out restarts)
    spec = FaultSpec(step_fail=0.01, token_corrupt=0.01, nan_logits=0.01,
                     alloc_fail=0.002, engine_crash=0.002, bank_fault=0.004,
                     prefix_drop=0.05)
    fault_budget = {"engine_crash": 4, "bank_fault": 12}

    def drive(plan):
        clock = FakeClock()
        cluster = ServeCluster(
            pool_pages=pool_pages, page_size=ps, clock=clock,
            policy=SchedPolicy(scheduler="drr", shed_busted=True,
                               preempt_busted=True),
            chaos=plan,
            watchdog=(FTConfig(max_restarts=64, backoff_base_s=1.0)
                      if plan is not None else None))
        for name, cfg, params, ns in (
                ("rep-a", cfg_a, params_a, cfg_a.name),
                ("rep-b", cfg_a, params_a, cfg_a.name),
                ("alt", cfg_b, params_b, cfg_b.name)):
            cluster.add_engine(cfg, params, name=name, namespace=ns,
                               slots=args.slots, max_len=max_len[name],
                               prefill_chunk=args.prefill_chunk,
                               queue_capacity=args.queue_capacity)
        trace = (Arrival(t, Request(id=rid, prompt=list(p),
                                    max_new_tokens=m, slo=slo, sampling=sp),
                         e)
                 for t, rid, p, m, slo, sp, e in base)
        sim = ClusterSimulator(cluster, trace, clock,
                               step_time=args.step_time,
                               dispatch_time=args.dispatch_time)
        w0 = time.perf_counter()
        report = sim.run(max_steps=5_000_000)
        wall = time.perf_counter() - w0
        metrics = ServeMetrics()
        tokens = {}
        for eng in cluster.engines.values():
            metrics.observe_all(eng.completed)
            tokens.update((r.id, tuple(r.tokens)) for r in eng.completed)
        # accounting: every submitted request lands in exactly one bucket
        done = sum(len(e.completed) for e in cluster.engines.values())
        dup = done - len(tokens)
        if dup:
            raise AssertionError(
                f"{dup} requests completed more than once under faults — "
                "crash re-admission must never duplicate finished work")
        total = done + report.rejected + report.shed
        if total != n:
            raise AssertionError(
                f"request accounting broke under faults: {done} completed "
                f"+ {report.rejected} rejected + {report.shed} shed = "
                f"{total} != {n} submitted — work was lost")
        return (report, metrics.summary(elapsed=report.elapsed),
                tokens, cluster, wall)

    rep0, sum0, tok0, cl0, wall0 = drive(None)
    plan1 = FaultPlan(args.seed, spec, budget=dict(fault_budget))
    rep1, sum1, tok1, cl1, wall1 = drive(plan1)

    def digest(report, summary, tokens, cluster):
        return (report.elapsed, report.steps, report.tokens_generated,
                report.rejected, report.shed, summary, tokens,
                cluster.stats()["faults"])

    if not args.chaos_skip_twin:
        plan2 = FaultPlan(args.seed, spec, budget=dict(fault_budget))
        rep2, sum2, tok2, cl2, _ = drive(plan2)
        if digest(rep1, sum1, tok1, cl1) != digest(rep2, sum2, tok2, cl2):
            raise AssertionError(
                "chaos run is not deterministic: two same-seed fault "
                "schedules diverged — every injection draw and every "
                "recovery must be seeded")
    common = tok0.keys() & tok1.keys()
    diverged = [i for i in sorted(common) if tok0[i] != tok1[i]]
    if diverged:
        raise AssertionError(
            f"{len(diverged)} requests completed with different tokens "
            f"under faults (e.g. {diverged[:3]}) — recovery must replay "
            "bit-identically")
    faults = cl1.stats()["faults"]
    if n >= 1_000:
        quiet = [k for k, c in plan1.counts.items() if c == 0]
        assert not quiet, f"fault kinds never injected at scale: {quiet}"
        assert faults["replays"] > 0, "no corruption quarantine replayed"
        assert faults["rebuilds"] > 0, "no crash rebuild engaged"

    goodput_retention = (sum1["goodput"] / sum0["goodput"]
                         if sum0["goodput"] else 0.0)
    overhead = ((rep1.elapsed - rep0.elapsed) / rep0.elapsed
                if rep0.elapsed else 0.0)

    def mode(tag, report, summary, cluster, wall):
        return {
            "mode": tag, "elapsed_sim": report.elapsed,
            "tokens": report.tokens_generated,
            "served": summary["completed"], "rejected": report.rejected,
            "shed": report.shed,
            "slo_attainment": round(summary["slo_attainment"], 4),
            "goodput_tok_per_sim_s": round(summary["goodput"], 4),
            "throughput_tok_per_sim_s": round(report.throughput, 4),
            "wall_s": round(wall, 3),
        }

    out = {"arch": cfg_a.name, "arch_b": cfg_b.name, "requests": n,
           "rate": rate, "process": args.open_loop_process, "engines": 3,
           "slots": args.slots, "queue_capacity": args.queue_capacity,
           "page_size": ps, "prefill_chunk": args.prefill_chunk,
           "dispatch_time": args.dispatch_time, "step_time": args.step_time,
           "fault_spec": dataclasses.asdict(spec),
           "fault_budget": fault_budget,
           "injected": dict(plan1.counts),
           "recovery": {k: faults[k] for k in
                        ("step_faults", "alloc_faults", "token_faults",
                         "replays", "retries", "crashes", "bank_faults",
                         "rebuilds")},
           "fault_free": mode("fault_free", rep0, sum0, cl0, wall0),
           "chaos": mode("chaos", rep1, sum1, cl1, wall1),
           "bit_identity": {
               "common_served": len(common),
               "diverged_vs_fault_free": 0,
               "duplicated": 0, "lost": 0,
           },
           "recovery_overhead_frac": round(overhead, 4),
           "goodput_retention": round(goodput_retention, 4),
           "deterministic": not args.chaos_skip_twin}
    if not args.json:
        for m in (out["fault_free"], out["chaos"]):
            print(f"{m['mode']:>10}: {m['served']} served / "
                  f"{m['rejected']} rejected / {m['shed']} shed of {n}; "
                  f"{m['tokens']} tokens in {m['elapsed_sim']:.0f} sim-s")
        inj = ", ".join(f"{k}={c}" for k, c in sorted(plan1.counts.items())
                        if c)
        print(f"injected: {inj}")
        print(f"recovery: {faults['retries']} retries, "
              f"{faults['replays']} replays, {faults['rebuilds']} rebuilds; "
              f"{len(common)} common requests bit-identical; "
              f"overhead {overhead:+.1%} sim-time, "
              f"goodput retention {goodput_retention:.1%}")
    return out, goodput_retention


def run_tp(args) -> tuple[dict, float]:
    """Mesh-sharded serving: single device vs tensor-parallel vs replicas.

    Three drives over one materialised open-loop arrival sequence (a
    shared-prefix greedy tenant plus a seeded sampled tenant):

    * ``single`` — one engine, no mesh (the PR 8 serving path);
    * ``tp`` — the same engine on a ``--tp``-device ``("model",)`` mesh:
      pool arenas and attention projections shard over the KV-head axis
      under ``shard_map``, and the decode all-gathers exactly once per
      step, before the output projection (``repro.serve.paged``);
    * ``replicas`` — a ``ServeCluster.add_replica_group`` of two
      tp-sharded members on disjoint device slices behind one group name,
      routed with prefix affinity (skipped by ``--tp-skip-replicas``).

    Every drive must produce bit-identical tokens per request — sharding
    and replication are memory/latency moves, never numerical ones. The
    report pairs aggregate tokens/s with the structural proof that the
    tp arenas *split* rather than duplicate: per-device arena bytes sum
    to the single-device footprint, ``1/tp`` of it on each device.
    """
    from repro.launch.mesh import replica_meshes, serve_tp_mesh
    from repro.serve.cluster import ServeCluster
    from repro.serve.loadgen import TenantSpec, open_loop_trace
    from repro.serve.sampling import SamplingParams
    from repro.serve.sim import Arrival, ClusterSimulator

    tp, n, rate = args.tp, args.tp_requests, args.open_loop_rate
    replicas = 0 if args.tp_skip_replicas else 2
    need = max(tp, replicas * tp)
    if len(jax.devices()) < need:
        raise SystemExit(
            f"--tp {tp} needs {need} devices, have {len(jax.devices())} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need} (see `make tp-smoke`)")
    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    params = P.init_tree(registry.decls(cfg), jax.random.key(args.seed))

    tenants = [
        TenantSpec(engine="pool", share=1.0, prompt_len=(6, 18),
                   new_tokens=(4, 10), prefix_len=8, prefix_seed=7),
        TenantSpec(engine="pool", share=0.5, prompt_len=(4, 12),
                   new_tokens=(4, 10),
                   sampling=SamplingParams(temperature=0.8, top_k=40)),
    ]
    max_len, ps = 32, 8
    base = [(a.time, a.request.id, tuple(a.request.prompt),
             a.request.max_new_tokens, a.request.sampling)
            for a in open_loop_trace(tenants, n_requests=n, rate=rate,
                                     seed=args.seed,
                                     process=args.open_loop_process)]

    def arrivals(engine=None):
        # fresh Request objects per drive: engines mutate their requests
        return (Arrival(t, Request(id=rid, prompt=list(p), max_new_tokens=m,
                                   sampling=sp), engine)
                for t, rid, p, m, sp in base)

    def drive_engine(mesh, tag, devices):
        clock = FakeClock()
        eng = ContinuousBatchingEngine(cfg, params, slots=args.slots,
                                       max_len=max_len, clock=clock,
                                       prefill_chunk=args.prefill_chunk,
                                       page_size=ps, mesh=mesh,
                                       queue_capacity=args.queue_capacity)
        sim = Simulator(eng, arrivals(), clock, step_time=args.step_time,
                        dispatch_time=args.dispatch_time)
        w0 = time.perf_counter()
        report = sim.run(max_steps=5_000_000)
        wall = time.perf_counter() - w0
        by_dev = eng._pool.bytes_by_device()
        return {"mode": tag, "devices": devices,
                "tokens": report.tokens_generated,
                "served": len(report.completed),
                "elapsed_sim": report.elapsed,
                "throughput_tok_per_sim_s": round(report.throughput, 4),
                "wall_s": round(wall, 3),
                "arena_bytes_by_device": by_dev}, _tokens(eng)

    single, tok_single = drive_engine(None, "single", 1)
    sharded, tok_tp = drive_engine(serve_tp_mesh(tp), f"tp{tp}", tp)
    if tok_tp != tok_single:
        raise AssertionError(
            "tensor-parallel decode diverged from single-device — the "
            "head-sharded step must be bit-identical")
    bytes_single = sum(single["arena_bytes_by_device"].values())
    by_dev = sharded["arena_bytes_by_device"]
    if len(by_dev) != tp or len(set(by_dev.values())) != 1:
        raise AssertionError(f"tp arena not evenly sharded: {by_dev}")
    if sum(by_dev.values()) != bytes_single:
        raise AssertionError(
            f"tp arenas duplicated instead of split: {sum(by_dev.values())} "
            f"bytes across {tp} devices vs {bytes_single} on one")

    out = {"arch": cfg.name, "tp": tp, "replicas": replicas, "requests": n,
           "rate": rate, "process": args.open_loop_process,
           "slots": args.slots, "max_len": max_len, "page_size": ps,
           "prefill_chunk": args.prefill_chunk,
           "dispatch_time": args.dispatch_time, "step_time": args.step_time,
           "single": single, "tp_sharded": sharded,
           "arena_bytes_single": bytes_single,
           "arena_bytes_per_device_tp": next(iter(by_dev.values())),
           "bit_identical": True}
    speedup = 1.0
    if replicas:
        clock = FakeClock()
        np_slot = -(-max_len // ps)
        cluster = ServeCluster(
            pool_pages=replicas * args.slots * np_slot + 16, page_size=ps,
            clock=clock)
        members = cluster.add_replica_group(
            cfg, params, name="pool", slots=args.slots, max_len=max_len,
            meshes=replica_meshes(replicas, tp),
            prefill_chunk=args.prefill_chunk,
            queue_capacity=args.queue_capacity)
        sim = ClusterSimulator(cluster, arrivals("pool"), clock,
                               step_time=args.step_time,
                               dispatch_time=args.dispatch_time)
        w0 = time.perf_counter()
        rep = sim.run(max_steps=5_000_000)
        wall = time.perf_counter() - w0
        tok_rep, per_member = {}, {}
        for m in members:
            tok_rep.update(_tokens(cluster.engines[m]))
            per_member[m] = len(cluster.engines[m].completed)
        # under queue_capacity overload two replica queues reject a
        # different subset than one single-engine queue, so compare the
        # requests both drives actually served — those must match exactly
        common = set(tok_rep) & set(tok_single)
        if not common:
            raise AssertionError("replica group served nothing in common "
                                 "with the single-device drive")
        if any(tok_rep[k] != tok_single[k] for k in common):
            raise AssertionError(
                "replica-group serving diverged from single-device — "
                "routing must never change a request's tokens")
        if not all(per_member.values()):
            raise AssertionError(f"router starved a replica: {per_member}")
        speedup = rep.throughput / single["throughput_tok_per_sim_s"]
        out["replica_group"] = {
            "mode": f"{replicas}x tp{tp}", "devices": replicas * tp,
            "members": per_member,
            "tokens": rep.tokens_generated,
            "served": sum(per_member.values()),
            "elapsed_sim": rep.elapsed, "rounds": rep.steps,
            "throughput_tok_per_sim_s": round(rep.throughput, 4),
            "wall_s": round(wall, 3),
            "arena_bytes_by_device": cluster.pool.bytes_by_device(),
        }
        out["replica_speedup_vs_single"] = round(speedup, 3)
    if not args.json:
        for m in [single, sharded] + ([out["replica_group"]] if replicas
                                      else []):
            print(f"{m['mode']:>8} [{m['devices']} device(s)]: "
                  f"{m['tokens']} tokens in {m['elapsed_sim']:.0f} sim-s "
                  f"({m['throughput_tok_per_sim_s']:.3f} tok/sim-s), "
                  f"wall {m['wall_s']:.2f}s")
        print(f"tp={tp} arenas: {out['arena_bytes_per_device_tp']} bytes on "
              f"each of {tp} devices vs {bytes_single} on one "
              f"(split, not duplicated); outputs bit-identical")
        if replicas:
            print(f"replica group vs single device: {speedup:.2f}x "
                  f"aggregate tokens/s over {replicas * tp} devices")
    return out, speedup


def run_kernel_bench(cfg, args) -> tuple[dict, float]:
    """Microbenchmark the fused paged-attention kernel vs its reference.

    On CPU the Pallas kernel runs in interpret mode, so the wall numbers
    track functional cost only — the artifact records both so a TPU run
    slots into the same JSON shape.
    """
    import numpy as np

    from repro.kernels.paged_attention import ops

    rng = np.random.default_rng(args.seed)
    h, kh, d = max(cfg.n_heads, 1), max(cfg.n_kv_heads, 1), cfg.resolved_head_dim
    b, ps = args.slots, args.page_size
    np_slot = -(-args.max_len // ps)
    pool_pages = b * np_slot + 1
    q = jax.numpy.asarray(rng.normal(size=(b, h, d)), jax.numpy.float32)
    kp = jax.numpy.asarray(rng.normal(size=(pool_pages, ps, kh * d)),
                           jax.numpy.float32)
    vp = jax.numpy.asarray(rng.normal(size=(pool_pages, ps, kh * d)),
                           jax.numpy.float32)
    tables = jax.numpy.asarray(
        rng.permutation(pool_pages - 1)[:b * np_slot].reshape(b, np_slot),
        jax.numpy.int32)
    lengths = jax.numpy.asarray(
        rng.integers(1, args.max_len, size=(b,)), jax.numpy.int32)

    def timed(impl):
        fn = jax.jit(lambda q, kp, vp: ops.paged_attention(
            q, kp, vp, tables, lengths, impl=impl))
        out = fn(q, kp, vp)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(args.kernel_iters):
            out = fn(q, kp, vp)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t0) / args.kernel_iters

    o_ref, t_ref = timed("ref")
    o_pal, t_pal = timed("pallas")
    err = float(jax.numpy.abs(o_ref - o_pal).max())
    out = {"arch": cfg.name, "slots": b, "heads": h, "kv_heads": kh,
           "head_dim": d, "page_size": ps, "pool_pages": pool_pages,
           "iters": args.kernel_iters, "max_abs_err": err,
           "ref_ms": round(t_ref * 1e3, 3),
           "pallas_interpret_ms": round(t_pal * 1e3, 3)}
    if not args.json:
        print(f"paged_attention ({b} slots, {pool_pages} pages, ps={ps}): "
              f"ref {out['ref_ms']}ms, pallas(interpret) "
              f"{out['pallas_interpret_ms']}ms, max |err| {err:.2e}")
    assert err < 1e-4, f"kernel diverged from reference: {err}"
    return out, 1.0


def run_energy(cfg, args) -> tuple[dict, float]:
    """Tokens/joule across power-management policies, one identical trace.

    Four same-seed drives of the async paged engine: an unmetered control,
    a metered engine with idle-bank clock gating *off* (the host-only
    baseline — idle banks burn full ON duty-0 power), the default metered
    engine (idle banks fall to gated leakage), and a DVFS-throttled engine
    pinned at the ``nominal`` operating point (lower voltage/frequency, the
    paper's §IV-D tradeoff). Outputs are asserted bit-identical across all
    four — metering and throttling change *when* energy is charged, never
    *what* the engine computes — and each metered drive's conservation
    invariant (total == attributed + overhead == Σ per-request µJ +
    overhead) is checked before any number is reported.
    """
    params = P.init_tree(registry.decls(cfg), jax.random.key(args.seed))
    n = args.energy

    def drive(mode, **engine_kwargs):
        reqs = build_requests(n, args.prompt_len, args.new_tokens)
        clock = FakeClock()
        eng = ContinuousBatchingEngine(cfg, params, slots=args.slots,
                                       max_len=args.max_len, clock=clock,
                                       prefill_chunk=args.prefill_chunk,
                                       async_dispatch=True, **engine_kwargs)
        sim = Simulator(eng, staggered_trace(reqs, gap=args.gap), clock,
                        step_time=args.step_time,
                        dispatch_time=args.dispatch_time)
        report = sim.run()
        entry = {"mode": mode, "tokens": report.tokens_generated,
                 "completed": len(report.completed)}
        if eng._meter is not None:
            st = eng.stats()["energy"]
            attributed = sum(r.energy_uj for r in report.completed)
            if not math.isclose(st["attributed_uj"], attributed,
                                rel_tol=1e-9):
                raise AssertionError(
                    f"{mode}: attributed energy {st['attributed_uj']} != "
                    f"Σ Request.energy_uj {attributed}")
            if not math.isclose(st["total_uj"],
                                st["attributed_uj"] + st["overhead_uj"],
                                rel_tol=1e-12):
                raise AssertionError(f"{mode}: energy conservation violated")
            entry.update(
                point=st["point"],
                total_uj=round(report.energy_uj, 3),
                uj_per_token=round(report.energy_uj
                                   / report.tokens_generated, 4),
                tokens_per_joule=round(report.tokens_per_joule, 1))
        return entry, eng

    control, eng_control = drive("control", metered=False)
    host_only, eng_host = drive("host-only", gate_idle_banks=False)
    gated, eng_gated = drive("clock-gated")
    dvfs, eng_dvfs = drive("dvfs-throttled", operating_point="nominal")
    _assert_identical([("control", eng_control), ("host-only", eng_host),
                       ("clock-gated", eng_gated),
                       ("dvfs-throttled", eng_dvfs)])

    gating_gain = gated["tokens_per_joule"] / host_only["tokens_per_joule"]
    dvfs_gain = dvfs["tokens_per_joule"] / gated["tokens_per_joule"]
    out = {"arch": cfg.name, "requests": n, "slots": args.slots,
           "gap": args.gap, "prompt_len": args.prompt_len,
           "new_tokens": args.new_tokens, "max_len": args.max_len,
           "prefill_chunk": args.prefill_chunk,
           "dispatch_time": args.dispatch_time, "step_time": args.step_time,
           "control": control, "host_only": host_only,
           "clock_gated": gated, "dvfs_throttled": dvfs,
           "gating_gain_tokens_per_joule": round(gating_gain, 3),
           "dvfs_gain_tokens_per_joule": round(dvfs_gain, 3)}
    if not args.json:
        for entry in (host_only, gated, dvfs):
            print(f"{entry['mode']:>15} [{entry['point']}]: "
                  f"{entry['tokens']} tokens, "
                  f"{entry['total_uj'] / 1e6:.4f} J, "
                  f"{entry['tokens_per_joule']:.1f} tokens/J "
                  f"({entry['uj_per_token']:.2f} uJ/token)")
        print(f"clock gating vs host-only: {gating_gain:.2f}x tokens/J; "
              f"DVFS nominal vs max: {dvfs_gain:.2f}x tokens/J; "
              f"outputs bit-identical across all four drives")
    return out, gated["tokens_per_joule"]


def _merge_bench_json(key: str, payload: dict) -> None:
    data = {}
    if BENCH_JSON.exists():
        data = json.loads(BENCH_JSON.read_text())
    data[key] = payload
    BENCH_JSON.write_text(json.dumps(data, indent=1, sort_keys=True) + "\n")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--gap", type=float, default=2.0)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=1,
                    help="prompt tokens fed per slot per step")
    ap.add_argument("--step-time", type=float, default=1.0,
                    help="sim cost of one batched device step")
    ap.add_argument("--dispatch-time", type=float, default=1.0,
                    help="sim cost of host scheduling per step (a sync "
                         "engine pays it serially; async overlaps it)")
    ap.add_argument("--shared-prefix", type=int, nargs="?", const=64,
                    default=0, metavar="LEN",
                    help="shared-prefix workload: compare the paged prefix "
                         "cache against no-sharing and the PR 2 engine")
    ap.add_argument("--tail-len", type=int, default=4,
                    help="distinct prompt tokens after the shared prefix")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per shared-prefix page")
    ap.add_argument("--sliding-window", type=int, nargs="?", const=16,
                    default=0, metavar="W",
                    help="sliding-window workload: the windowed paged "
                         "backend (ring block tables) vs the lane ring "
                         "cache")
    ap.add_argument("--open-loop", type=int, nargs="?", const=100_000,
                    default=0, metavar="N",
                    help="open-loop workload: N lazily generated arrivals "
                         "(SLO-aware scheduling vs flat WRR on goodput)")
    ap.add_argument("--open-loop-rate", type=float, default=100.0,
                    help="mean arrival rate (requests per sim-s) of the "
                         "open-loop trace")
    ap.add_argument("--open-loop-process", default="bursty",
                    choices=("poisson", "bursty", "diurnal"),
                    help="arrival process of the open-loop trace")
    ap.add_argument("--queue-capacity", type=int, default=48,
                    help="per-engine queue bound of the open-loop cluster "
                         "(beyond it, arrivals are rejected)")
    ap.add_argument("--open-loop-skip-flat", action="store_true",
                    help="skip the flat-WRR comparison run (smoke tier: "
                         "determinism pair only)")
    ap.add_argument("--sampling", type=int, nargs="?", const=2000,
                    default=0, metavar="N",
                    help="sampling workload: N open-loop arrivals with "
                         "stochastic tenants — two same-seed runs must be "
                         "bit-identical, sampled streams must diverge from "
                         "greedy, greedy neighbours must not")
    ap.add_argument("--chaos", type=int, nargs="?", const=2000,
                    default=0, metavar="N",
                    help="chaos workload: N open-loop arrivals served "
                         "fault-free, under a seeded fault plan, and under "
                         "the same plan again — bit-identity, single "
                         "accounting, and schedule determinism are "
                         "asserted before any number is reported")
    ap.add_argument("--chaos-skip-twin", action="store_true",
                    help="skip the same-seed determinism twin drive "
                         "(smoke tier: fault-free vs chaos bit-identity "
                         "only)")
    ap.add_argument("--energy", type=int, nargs="?", const=1000, default=0,
                    metavar="N",
                    help="energy workload: N staggered requests driven "
                         "through four power-management variants (unmetered "
                         "control, host-only, clock-gated, DVFS-throttled) "
                         "— bit-identity and per-request joule conservation "
                         "are asserted, tokens/joule reported per variant")
    ap.add_argument("--tp", type=int, nargs="?", const=2, default=0,
                    metavar="N",
                    help="sharded workload: single device vs N-way "
                         "head-sharded tensor parallelism vs a 2-replica "
                         "group of N-way members — bit-identity asserted, "
                         "per-device arena bytes reported (needs "
                         "XLA_FLAGS=--xla_force_host_platform_device_count)")
    ap.add_argument("--tp-requests", type=int, default=600,
                    help="open-loop arrivals of the --tp workload")
    ap.add_argument("--tp-skip-replicas", action="store_true",
                    help="skip the replica-group drive (smoke tier: "
                         "single vs tp only, needs just N devices)")
    ap.add_argument("--kernel-bench", action="store_true",
                    help="microbenchmark the paged-attention kernel vs ref")
    ap.add_argument("--kernel-iters", type=int, default=20)
    ap.add_argument("--multi-model", action="store_true",
                    help="multi-model cluster workload: two models / three "
                         "engines on one shared pool vs isolated engines")
    ap.add_argument("--arch-b", default="stablelm-3b",
                    help="second model of the --multi-model cluster")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--bench-json", action="store_true",
                    help="merge this run's report into BENCH_serve.json")
    args = ap.parse_args(argv)

    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)

    if args.kernel_bench:
        out, speedup = run_kernel_bench(cfg, args)
        tag, key = "__kernel", "kernel"
    elif args.tp:
        out, speedup = run_tp(args)
        tag, key = "__tp", "sharded"
    elif args.chaos:
        out, speedup = run_chaos(args)
        tag, key = "__chaos", "chaos"
    elif args.energy:
        out, speedup = run_energy(cfg, args)
        tag, key = "__energy", "energy"
    elif args.sampling:
        out, speedup = run_sampling(args)
        tag, key = "__sampling", "sampling"
    elif args.open_loop:
        out, speedup = run_open_loop(args)
        tag, key = "__open_loop", "open_loop"
    elif args.multi_model:
        out, speedup = run_multi_model(args)
        tag, key = "__multi_model", "multi_model"
    elif args.sliding_window:
        out, speedup = run_sliding_window(args)
        tag, key = "__sliding_window", "sliding_window"
    else:
        params = P.init_tree(registry.decls(cfg), jax.random.key(args.seed))
        if args.shared_prefix:
            out, speedup = run_shared_prefix(cfg, params, args)
            tag, key = "__shared_prefix", "shared_prefix"
        else:
            out, speedup = run_default(cfg, params, args)
            tag, key = "__trace", "default"
    if args.json:
        print(json.dumps(out, indent=1))
    if args.bench_json:
        _merge_bench_json(key, out)
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"{cfg.name}{tag}.json").write_text(json.dumps(out, indent=1))
    return speedup


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
