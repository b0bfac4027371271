"""Bring-up check: serve granite-3-2b at its published widths on a TPU.

  python chip_smoke.py                # one chip
  python chip_smoke.py --four-chips   # one chip vs tp=4 vs 4 replicas

granite-3-2b (40 layers, d_model 2048, 32 query / 8 KV heads, d_ff 8192,
vocab 49155) gets random bf16 weights from ``--seed`` and serves eight
requests through :class:`ContinuousBatchingEngine` on the paged backend
(8 slots, max_len 1024, 16-token pages): four share a 128-token prefix,
four share nothing. One chip checks that

* every request completes with exactly ``max_new_tokens`` in-vocab tokens,
  with shared prefix pages reused, no replays and no token faults;
* the compiled decode step holds the Pallas kernel (``tpu_custom_call``),
  so the kernel ran compiled, not interpreted;
* one full-width paged step through the kernel and through the pure-jax
  ``ref`` gives the same logits within ``LOGIT_RTOL``, over the first
  ``COMPARE_LAYERS`` layers of the same weights and pool.

``--four-chips`` runs only the sharded paths and what they are compared
with: the same requests on one chip, on a tp=4 engine, and on a group of
four tp=1 replicas; greedy tokens must match the one-chip run (logits
within ``LOGIT_RTOL`` where they do not), and the pool bytes must sit
where the mesh says.

The seconds and bytes printed along the way are one run, not a benchmark.
The last line is one JSON object naming the device, printed only when
every check passed on a TPU; without one the script exits non-zero.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.kernels import backend  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import replica_meshes, serve_tp_mesh  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.serve import paged  # noqa: E402
from repro.serve.cluster import ServeCluster  # noqa: E402
from repro.serve.engine import ContinuousBatchingEngine, Request  # noqa: E402
from repro.sharding import params as P  # noqa: E402
from repro.sharding import rules as R  # noqa: E402

ARCH = "granite_3_2b"
SLOTS, MAX_LEN, PAGE_SIZE, NEW_TOKENS = 8, 1024, 16, 32
PREFIX_LEN = 128
SHARED_TAILS = (32, 64, 96, 128)     # own tokens after the shared prefix
UNSHARED_LENS = (64, 192, 320, 512)
# Two implementations of one bf16 step: each layer rounds its attention
# output to bf16, so they may part by a bf16 ulp (2^-8 relative) there.
# Random weights make the 40-layer stack chaotic, so a full-depth
# comparison measures the weights, not the kernel. On one TPU v5e at
# published widths, on a served pool, by depth 1/2/4/8/40, as % of the
# largest logit: kernel vs ref 0.36/1.0/13/72/118; ref vs ref with one
# bf16 ulp added to half of layer 0's V pool, no kernel involved,
# 0.71/7.3/48/93/124. Logits are compared after the first COMPARE_LAYERS
# layers, at full width, where they must agree within 2%; a planted kernel
# fault (the appended token dropped, one wrong page, one head zeroed)
# parts them by 46-86% there.
COMPARE_LAYERS = 2
LOGIT_RTOL = 2e-2
MAX_STEPS = 10_000


def check(ok: bool, what) -> None:
    """Fail the run when ``ok`` is false (an ``assert`` that ``-O`` keeps)."""
    if not ok:
        raise AssertionError(what)


def make_requests(vocab: int, seed: int) -> list[Request]:
    """Four requests sharing a 128-token prefix (plus 32-128 own tokens)
    and four with unshared 64-512-token prompts, each asking for
    ``NEW_TOKENS`` tokens; token ids drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, PREFIX_LEN).tolist()
    reqs = [Request(f"shared{i}", prefix + rng.integers(0, vocab, n).tolist(),
                    NEW_TOKENS) for i, n in enumerate(SHARED_TAILS)]
    reqs += [Request(f"solo{i}", rng.integers(0, vocab, n).tolist(),
                     NEW_TOKENS) for i, n in enumerate(UNSHARED_LENS)]
    return reqs


def init_params(cfg, seed: int):
    """bf16 weights from ``seed``, materialised leaf by leaf on device."""
    return jax.block_until_ready(P.init_tree(
        registry.decls(cfg), jax.random.key(seed), dtype=jnp.bfloat16))


def build_engine(cfg, params, mesh=None) -> ContinuousBatchingEngine:
    return ContinuousBatchingEngine(
        cfg, params, slots=SLOTS, max_len=MAX_LEN, page_size=PAGE_SIZE,
        clock=time.perf_counter, mesh=mesh)


def compile_step(engine: ContinuousBatchingEngine) -> tuple[str, float]:
    """Compile the engine's paged decode step for its own argument shapes
    (the arguments ``_launch`` passes, every lane masked); returns the
    compiled HLO text and the seconds the compile took."""
    n = engine.n_lanes
    tables, lengths = engine._build_tables()
    off = jnp.zeros((n,), bool)
    args = (engine.params, engine._arena.k, engine._arena.v, engine._state,
            tables, lengths,
            jnp.zeros((n,), jnp.int32), off, engine._zero_prev, off, off,
            engine._keys, jnp.asarray(engine._temp),
            jnp.asarray(engine._topk), jnp.asarray(engine._topp))
    t = time.perf_counter()
    compiled = engine._pstep.lower(*args).compile()
    return compiled.as_text(), time.perf_counter() - t


def serve(engine: ContinuousBatchingEngine, step, requests) -> dict:
    """Submit ``requests``, call ``step()`` until idle; returns tokens by
    request id plus the wall seconds and steps it took."""
    for r in requests:
        if not engine.submit(r):
            raise AssertionError(f"request {r.id} rejected")
    t = time.perf_counter()
    steps = 0
    while step():
        steps += 1
        if steps > MAX_STEPS:
            raise AssertionError(f"still busy after {MAX_STEPS} steps")
    return {"tokens": {r.id: list(r.tokens) for r in requests},
            "wall_s": time.perf_counter() - t, "steps": steps}


def check_served(requests, stats: dict, vocab: int) -> None:
    """Every request complete with exactly its token budget, all in
    vocab; prefix pages reused; nothing replayed and no token refused
    (the corruption quarantine must not hide a kernel that emits
    garbage)."""
    for r in requests:
        check(r.finish_time is not None, f"{r.id} did not complete")
        check(len(r.tokens) == r.max_new_tokens, (r.id, len(r.tokens)))
        check(all(0 <= t < vocab for t in r.tokens), (r.id, r.tokens))
    check(stats["prompt_tokens_reused"] > 0, stats)
    check(stats["replays"] == 0, stats)
    check(stats["token_faults"] == 0, stats)


def _random_step_inputs(cfg, n_pages: int, seed: int, pool_k=None,
                        pool_v=None):
    """Tables, lengths and tokens for one full-width paged step whose
    tables pick among the first ``n_pages`` pages of each layer (random
    bf16 K/V in a flat arena of ``n_pages`` a layer when no pool is
    given)."""
    rng = np.random.default_rng(seed)
    n_entries = -(-MAX_LEN // PAGE_SIZE)
    if pool_k is None:
        kk, kv = jax.random.split(jax.random.key(seed))
        shape = (cfg.n_layers * n_pages, PAGE_SIZE,
                 cfg.n_kv_heads * cfg.resolved_head_dim)
        pool_k = jax.random.normal(kk, shape, jnp.bfloat16)
        pool_v = jax.random.normal(kv, shape, jnp.bfloat16)
    tables = jnp.asarray(rng.integers(0, n_pages, (SLOTS, n_entries)),
                         jnp.int32)
    lengths = jnp.asarray(rng.integers(PAGE_SIZE, MAX_LEN - 1, SLOTS),
                          jnp.int32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, SLOTS), jnp.int32)
    return pool_k, pool_v, tables, lengths, toks


def first_layers(cfg, params, n: int = COMPARE_LAYERS):
    """The config and weights cut to their first ``n`` layers (all of
    them when the model has no more), widths unchanged."""
    n = min(n, cfg.n_layers)
    blocks = jax.tree.map(lambda a: a[:n], params["blocks"])
    return dataclasses.replace(cfg, n_layers=n), dict(params, blocks=blocks)


def logit_error(got, want) -> dict:
    """Largest logit difference relative to the largest reference logit,
    plus how many rows keep their argmax."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    same = int((got.argmax(-1) == want.argmax(-1)).sum())
    return {"rel_err": rel, "argmax_same": f"{same}/{len(want)}"}


def kernel_vs_ref(cfg, params, engine, seed: int) -> dict:
    """One full-width paged step through the Pallas kernel and through
    ``ref`` on the engine's own pool (pages the run wrote) and the same
    tables, lengths and tokens, over the first COMPARE_LAYERS layers;
    logits must agree within LOGIT_RTOL."""
    used = max(engine._pool.stats["high_water"], 1)
    cfg, params = first_layers(cfg, params)
    # the arena's rows of the first layers: n_pages + 1 (the null) a layer
    rows = cfg.n_layers * (engine._pool.n_pages + 1)
    pool_k, pool_v, tables, lengths, toks = _random_step_inputs(
        cfg, used, seed, engine._arena.k[:rows], engine._arena.v[:rows])
    logits = {}
    for impl in ("pallas", "ref"):
        fn = jax.jit(lambda p, k, v, t, n, x, impl=impl:
                     registry.decode_step_paged(p, cfg, k, v, t, n, x,
                                                impl=impl)[0])
        logits[impl] = fn(params, pool_k, pool_v, tables, lengths, toks)
    err = logit_error(logits["pallas"], logits["ref"])
    check(err["rel_err"] <= LOGIT_RTOL, err)
    return err


def sharded_vs_single(cfg, params, mesh, seed: int) -> dict:
    """One full-width paged step on one device and the same step on
    ``mesh`` (params and pool placed as the engine places them), on the
    same random pool and inputs, over the first COMPARE_LAYERS layers;
    logits must agree within LOGIT_RTOL."""
    n_pages = SLOTS * -(-MAX_LEN // PAGE_SIZE) + 1
    cfg, params = first_layers(cfg, params)
    pool_k, pool_v, tables, lengths, toks = _random_step_inputs(
        cfg, n_pages, seed)
    single = jax.jit(lambda p, k, v, t, n, x: registry.decode_step_paged(
        p, cfg, k, v, t, n, x)[0])(params, pool_k, pool_v, tables, lengths,
                                   toks)
    spec = jax.sharding.NamedSharding(mesh, R.serve_pool_spec("model"))
    call = paged._decode_call(cfg, None, mesh, "model")
    sharded = jax.jit(lambda *a: call(*a)[0])(
        paged.place_params(cfg, params, mesh), jax.device_put(pool_k, spec),
        jax.device_put(pool_v, spec), None, tables, lengths, toks,
        jnp.ones((SLOTS,), bool))
    err = logit_error(sharded, single)
    check(err["rel_err"] <= LOGIT_RTOL, err)
    return err


def same_or_close(name: str, got: dict, want: dict, cfg, params, mesh,
                  seed: int, log) -> None:
    """Greedy tokens must match the one-chip run bitwise; where they do
    not, the sharded step's logits must match the single-device step's
    within LOGIT_RTOL."""
    same = sum(got[k] == want[k] for k in want)
    log(f"{name}: {same}/{len(want)} requests token-identical to one chip")
    if same < len(want):
        log(f"{name}: logits vs one chip {sharded_vs_single(cfg, params, mesh, seed)}")


def memory_report(devices=None) -> dict:
    """Peak and current bytes per device, where the backend reports them."""
    out = {}
    for d in devices or jax.devices()[:1]:
        s = d.memory_stats() or {}
        out[str(d)] = {k: s.get(k) for k in ("peak_bytes_in_use",
                                             "bytes_in_use", "bytes_limit")}
    return out


def run_one_chip(cfg, seed: int = 0, log=print) -> dict:
    """The one-chip bring-up: serve, check, HLO check, kernel vs ref.
    Takes the config so a CPU test can drive it at smoke widths."""
    t = time.perf_counter()
    params = init_params(cfg, seed)
    engine = build_engine(cfg, params)
    setup_s = time.perf_counter() - t
    hlo, compile_s = compile_step(engine)
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}; {cfg.param_count()} params in bf16")
    log(f"setup {setup_s:.2f} s (params + engine), step compile "
        f"{compile_s:.2f} s")
    has_kernel = "tpu_custom_call" in hlo
    log(f"paged step holds tpu_custom_call: {has_kernel}")
    # the compiled kernel exactly when on a TPU; the bit-exact ref elsewhere
    check(has_kernel == backend.on_tpu(), has_kernel)

    requests = make_requests(cfg.vocab, seed)
    run = serve(engine, engine.step, requests)
    stats = engine.stats()
    check_served(requests, stats, cfg.vocab)
    log(f"served {len(requests)} requests in {run['wall_s']:.2f} s, "
        f"{run['steps']} steps, {stats['tokens_generated']} tokens "
        f"generated, {stats['prompt_tokens_processed']} prompt tokens "
        f"computed, {stats['prompt_tokens_reused']} reused, replays "
        f"{stats['replays']}, token faults {stats['token_faults']} "
        "(one run, not a benchmark)")
    err = kernel_vs_ref(cfg, params, engine, seed)
    log(f"kernel vs ref logits: {err} (tolerance {LOGIT_RTOL})")
    mem = memory_report()
    log(f"device memory: {mem}")
    return {"tokens": run["tokens"], "stats": stats, "kernel_vs_ref": err,
            "setup_s": setup_s, "compile_s": compile_s, "memory": mem}


def run_four_chips(cfg, seed: int = 0, log=print) -> dict:
    """One chip vs a tp=4 engine vs four tp=1 replicas on the same
    requests. Each phase's engines (and the weights placed for them)
    are dropped before the next phase starts."""
    devices = jax.devices()[:4]
    check(len(devices) == 4, devices)
    params = init_params(cfg, seed)

    engine = build_engine(cfg, params)
    compile_step(engine)
    requests = make_requests(cfg.vocab, seed)
    base = serve(engine, engine.step, requests)
    check_served(requests, engine.stats(), cfg.vocab)
    single_bytes = sum(engine._pool.bytes_by_device().values())
    log(f"one chip: {base['steps']} steps in {base['wall_s']:.2f} s")
    del engine
    gc.collect()
    log(f"weights only: {memory_report(devices)}")

    mesh = serve_tp_mesh(4)
    engine = build_engine(cfg, params, mesh=mesh)
    _, compile_s = compile_step(engine)
    requests = make_requests(cfg.vocab, seed)
    tp = serve(engine, engine.step, requests)
    check_served(requests, engine.stats(), cfg.vocab)
    by_dev = engine._pool.bytes_by_device()
    log(f"tp=4: compile {compile_s:.2f} s, {tp['steps']} steps in "
        f"{tp['wall_s']:.2f} s; arena bytes by device {by_dev}")
    # the arena is split over the four devices, not copied to each
    check(len(by_dev) == 4 and len(set(by_dev.values())) == 1, by_dev)
    check(sum(by_dev.values()) == single_bytes, (by_dev, single_bytes))
    log(f"tp=4 device memory: {memory_report(devices)}")
    del engine
    gc.collect()
    same_or_close("tp=4", tp["tokens"], base["tokens"], cfg, params, mesh,
                  seed, log)

    meshes = replica_meshes(4, 1)
    cluster = ServeCluster(pool_pages=SLOTS * -(-MAX_LEN // PAGE_SIZE)
                           + 16 * SLOTS, page_size=PAGE_SIZE,
                           clock=time.perf_counter)
    members = cluster.add_replica_group(cfg, params, name="granite",
                                        slots=SLOTS, max_len=MAX_LEN,
                                        meshes=meshes)
    requests = make_requests(cfg.vocab, seed)
    for r in requests:
        check(cluster.submit("granite", r), r.id)
    t = time.perf_counter()
    cluster.run_until_idle(MAX_STEPS)
    wall = time.perf_counter() - t
    stats = {k: sum(cluster.engines[m].stats()[k] for m in members)
             for k in ("prompt_tokens_reused", "replays", "token_faults")}
    check_served(requests, stats, cfg.vocab)
    by_dev = cluster.pool.bytes_by_device()
    served = {m: len(cluster.engines[m].completed) for m in members}
    log(f"4 replicas: served {served} in {wall:.2f} s; arena bytes by "
        f"device {by_dev}")
    # one arena per replica, each on that replica's own device, holding
    # that replica's weights too
    check(len(by_dev) == 4 and set(by_dev.values()) == {single_bytes}, by_dev)
    for m, mesh_i in zip(members, meshes):
        held = {d for leaf in jax.tree.leaves(cluster.engines[m].params)
                for d in leaf.devices()}
        check(held == set(mesh_i.devices.flat), (m, held))
    log(f"replica device memory: {memory_report(devices)}")
    del cluster
    gc.collect()
    same_or_close("4 replicas", {r.id: list(r.tokens) for r in requests},
                  base["tokens"], cfg, params, meshes[1], seed, log)
    log(f"after the last phase: {memory_report(devices)}")
    return {"base": base, "tp": tp}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=4 and 4-replica paths and the "
                         "one-chip run they are compared with")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    log = functools.partial(print, flush=True)
    log(f"compile cache: {enable_compile_cache()}")
    cfg = configs.get(ARCH)
    if args.four_chips:
        run_four_chips(cfg, args.seed, log)
    else:
        run_one_chip(cfg, args.seed, log)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
