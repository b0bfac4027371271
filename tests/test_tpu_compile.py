"""Ahead-of-time TPU compiles of the paged decode at real widths.

The TPU compiler is installed even where no TPU is attached, so these
tests lower for a described ``v5e:2x2`` topology and compile for its
chips. That catches what interpret mode cannot: block shapes the TPU
tiling refuses, scratch that does not fit VMEM, and copies the compiler
adds around the Pallas call.

Two kinds of case, one test:

* the kernel alone, at granite-3-2b's decode widths (8 slots, 32 query /
  8 KV heads, head dim 64, 16-token pages, max_len 1024 = 64 table
  entries) on the flat ``(P', page, Kh·Dh)`` page space: a contiguous
  table, a 256-token sliding window, and the per-device head slice of
  tp=4 (8 / 2 heads);
* the whole paged step (``decode_step_paged``, 4 layers) with the arena
  donated: granite widths at 32 slots, stablelm-3b widths (32 / 32 heads,
  head dim 80) at 8 slots, and granite at tp=4 under ``shard_map`` on the
  four described chips, each device holding its head slice of the arena.
  The step must update the arena in place: no ``copy``, ``copy-start``,
  ``dynamic-slice`` or ``dynamic-update-slice`` shaped like the arena or
  one layer's pool, and temporaries under 1% of the arena's bytes.

A third case compiles granite-4.0-h-micro's paged step at one period of
its layer pattern (9 Mamba-2 layers and 1 attention layer) and 64 slots,
with the KV arena (the attention layer's pages only) and the state arena
donated: the ``ssm_decode`` kernel updates each layer's state rows in
place, so no copy, slice or update of the state arena (or of one layer's
state) appears, no move of the KV arena, and the temporaries stay under
1% of the two arenas.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports
every test file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro import configs
from repro.kernels import backend
from repro.kernels.paged_attention.kernel import paged_attention_kernel
from repro.models import hybrid, registry
from repro.serve import paged
from repro.sharding import params as P
from repro.sharding import rules as R

SLOTS, HEAD_DIM, PAGE, ENTRIES = 8, 64, 16, 64
N_PAGES = SLOTS * ENTRIES + 1
STEP_LAYERS = 4
# ops that would move a whole arena or one layer's pool
_MOVES = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_kernel(topo, heads, kv_heads, window):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    entries = ENTRIES if window is None else -(-window // PAGE) + 1
    pool = spec((N_PAGES, PAGE, kv_heads * HEAD_DIM), jnp.bfloat16)
    args = (spec((SLOTS, heads, HEAD_DIM), jnp.bfloat16), pool, pool,
            spec((SLOTS, entries), jnp.int32), spec((SLOTS,), jnp.int32))

    def step(q, k, v, tables, lengths):
        return paged_attention_kernel(q, k, v, tables, lengths,
                                      window=window, interpret=False)

    return jax.jit(step).lower(*args).compile()


def _compile_step(topo, monkeypatch, arch, slots, tp):
    """The paged step of ``arch`` at ``STEP_LAYERS`` layers, arena donated,
    on one described chip or under ``shard_map`` on ``tp`` of them; returns
    the compiled step and the arena's page count a layer."""
    # the step picks the compiled kernel from the backend it runs on; here
    # that is the CPU, so steer it to the chip being compiled for
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    cfg = dataclasses.replace(configs.get(arch), n_layers=STEP_LAYERS)
    rows = slots * ENTRIES + 1
    arena = (STEP_LAYERS * rows, PAGE, cfg.n_kv_heads * cfg.resolved_head_dim)
    decls = registry.decls(cfg)
    if tp == 1:
        one_chip = SingleDeviceSharding(topo.devices[0])
        params = P.map_decls(lambda d: jax.ShapeDtypeStruct(
            d.shape, jnp.bfloat16, sharding=one_chip), decls)
        pool_sh = rep = one_chip
        fn = lambda p, k, v, t, n, x: registry.decode_step_paged(  # noqa: E731
            p, cfg, k, v, t, n, x)
    else:
        mesh = Mesh(np.asarray(topo.devices[:tp]), ("model",))
        params = jax.tree.map(lambda d, s: jax.ShapeDtypeStruct(
            d.shape, jnp.bfloat16, sharding=NamedSharding(mesh, s)),
            decls, R.serve_param_specs(cfg, "model"), is_leaf=P.is_decl)
        pool_sh = NamedSharding(mesh, R.serve_pool_spec("model"))
        rep = NamedSharding(mesh, PartitionSpec())
        call = paged._decode_call(cfg, None, mesh, "model")
        fn = lambda p, k, v, t, n, x: call(  # noqa: E731
            p, k, v, None, t, n, x, jnp.ones(x.shape, bool))
    pool = jax.ShapeDtypeStruct(arena, jnp.bfloat16, sharding=pool_sh)
    args = (params, pool, pool,
            jax.ShapeDtypeStruct((slots, ENTRIES), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=rep))
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile()
    return compiled, rows, 2 * np.prod(arena) * 2 // tp


def _arena_moves(text, rows):
    """HLO lines that copy, slice or update a buffer with the arena's page
    count (``STEP_LAYERS`` × ``rows``) or one layer's (``rows``) in its
    shape."""
    sizes = {rows, STEP_LAYERS * rows}
    out = []
    for line in text.splitlines():
        m = re.search(r"= (.*?) (%s)\(" % "|".join(_MOVES), line)
        if m and sizes & {int(n) for shape in re.findall(r"\[([\d,]*)\]",
                                                           m.group(1))
                          for n in shape.split(",") if n}:
            out.append(line.strip()[:200])
    return out


@pytest.mark.parametrize("what,arg,window", [
    ("kernel", (32, 8), None),             # contiguous block table
    ("kernel", (32, 8), 256),              # ring table of a 256-token window
    ("kernel", (8, 2), None),              # one device's head slice at tp=4
    ("step", ("granite_3_2b", 32, 1), None),
    ("step", ("stablelm_3b", 8, 1), None),
    ("step", ("granite_3_2b", 32, 4), None),   # shard_map over 4 chips
], ids=["contiguous", "window256", "tp4_slice", "step_granite",
        "step_stablelm", "step_tp4"])
def test_paged_attention_kernel_compiles_for_v5e(topo, monkeypatch, what,
                                                 arg, window):
    if what == "kernel":
        compiled = _compile_kernel(topo, *arg, window)
    else:
        compiled, rows, arena_bytes = _compile_step(topo, monkeypatch, *arg)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the call is named, so a profile tells it from any other Pallas call
    assert re.search(r"%paged_attention[.\d]* = .*"
                     r'custom_call_target="tpu_custom_call"', text)
    if what == "step":
        assert _arena_moves(text, rows) == []
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 0.01 * arena_bytes, (temp, arena_bytes)


def test_hybrid_paged_step_keeps_both_arenas_in_place(topo, monkeypatch):
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    base = configs.get("granite_4_0_h_micro")
    period = len(hybrid.period(base))
    cfg = dataclasses.replace(base, n_layers=period,
                              layer_types=base.layer_types[:period])
    slots = 64
    rows = slots * ENTRIES + 1
    one_chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = P.map_decls(lambda d: spec(d.shape, jnp.bfloat16),
                         registry.decls(cfg))
    pool = spec((hybrid.n_attention(cfg) * rows, PAGE,
                 cfg.n_kv_heads * cfg.resolved_head_dim), jnp.bfloat16)
    state = jax.tree.map(lambda a: spec(a.shape, a.dtype),
                         jax.eval_shape(lambda: registry.state_init(cfg, slots)))
    ints = spec((slots,), jnp.int32)

    def step(p, k, v, st, t, n, x):
        return registry.decode_step_paged(p, cfg, k, v, t, n, x, state=st)

    compiled = jax.jit(step, donate_argnums=(1, 2, 3)).lower(
        params, pool, pool, state, spec((slots, ENTRIES), jnp.int32), ints,
        ints).compile()
    text = compiled.as_text()
    calls = re.findall(r"%ssm_decode[.\d]* = .*"
                       r'custom_call_target="tpu_custom_call"', text)
    assert len(calls) == hybrid.n_mamba(cfg)
    for scope in ("ssm.in_proj", "ssm.conv", "ssm.kernel", "ssm.out"):
        assert f"/{scope}/" in text, scope
    assert re.search(r"%paged_attention[.\d]* = ", text)
    assert _arena_moves(text, rows) == []
    layer = ",".join(map(str, state.state.shape[1:]))
    moves = [line.strip()[:200] for line in text.splitlines()
             if re.search(r"= f32\[(\d+,)?%s\]\S* (%s)\(" % (
                 layer, "|".join(_MOVES)), line)]
    assert moves == []
    arenas = 2 * np.prod(pool.shape) * 2 + sum(
        np.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(state))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.01 * arenas, (temp, arenas)
