"""Sharding rules vs serving configs: every paged config resolves TP.

The serving tensor-parallel path (:func:`repro.sharding.rules.
validate_serve_tp` + ``serve_param_specs`` + ``serve_pool_spec``) must
agree with the registry about which configs it can shard and how:

* every paged-capable config resolves a valid head-axis sharding for any
  ``tp`` that divides its KV-head count — and the resolved spec tree
  shards exactly the into-head projections (Axes ending in HEAD_DIM),
  leaving the output projection replicated so the decode step's one
  collective stays the pre-``wo`` all-gather;
* indivisible head counts (GQA at too-large tp, MQA at any tp > 1) are
  rejected *loudly* with the cause in the message — the serving
  counterpart of ``spec_for``'s silent divisibility fallback, which would
  quietly replicate arenas the caller asked to split;
* MoE and the SSM/hybrid lane-fallback families are rejected at any tp
  (no paged KV, no head axis to shard), again naming the reason; the
  Mamba-2 hybrid, paged with a per-slot state arena, at any tp > 1.
"""

import dataclasses

import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.models import registry
from repro.sharding import axes as lx
from repro.sharding import rules as R
from repro.sharding.params import Axes, axes_tree, is_axes, map_decls

# head-sharded serving: paged configs without per-slot recurrent state
PAGED = [a for a in configs.names()
         if registry.supports_paged(configs.smoke(a))
         and not registry.holds_state(configs.smoke(a))]
UNPAGED = [a for a in configs.names()
           if not registry.supports_paged(configs.smoke(a))]


def _spec_leaves(cfg, tp_axis="model"):
    """(axes, spec) pairs over the config's parameter tree."""
    import jax

    axt = axes_tree(registry.decls(cfg))
    spt = R.serve_param_specs(cfg, tp_axis)
    axs = jax.tree.leaves(axt, is_leaf=is_axes)
    sps = jax.tree.leaves(spt, is_leaf=lambda x: isinstance(x, P))
    assert len(axs) == len(sps)
    return list(zip(axs, sps))


@pytest.mark.parametrize("arch", PAGED)
def test_paged_configs_resolve_head_sharding(arch):
    """Every paged-capable config validates at every tp dividing its KV
    heads, and its spec tree shards the head axis of exactly the
    into-head projections."""
    cfg = configs.smoke(arch)
    for tp in (1, 2, cfg.n_kv_heads):
        if cfg.n_kv_heads % tp == 0:
            R.validate_serve_tp(cfg, tp)    # must not raise
    # q heads are groups x kv heads, so kv divisibility implies q
    assert cfg.n_heads % cfg.n_kv_heads == 0
    sharded = 0
    for ax, spec in _spec_leaves(cfg):
        dims = tuple(ax)
        if "model" in spec:
            sharded += 1
            assert dims[-1] == lx.HEAD_DIM, (dims, spec)
            assert dims[spec.index("model")] in (lx.HEADS, lx.KV_HEADS)
            assert spec.count("model") == 1
        elif dims and dims[-1] == lx.HEAD_DIM:
            # an into-head projection left replicated would silently
            # duplicate attention compute across the mesh
            assert not ({lx.HEADS, lx.KV_HEADS} & set(dims)), (dims, spec)
    # wq + wk + wv (layer-stacked decls: one leaf each, LAYERS-leading)
    assert sharded >= 3


@pytest.mark.parametrize("arch", PAGED)
def test_output_projection_stays_replicated(arch):
    """wo consumes the all-gathered heads: its spec must be empty even
    though its axes mention HEADS — the HEAD_DIM-suffix rule, not a name
    denylist, is what distinguishes it."""
    cfg = configs.smoke(arch)
    specs = R.serve_param_specs(cfg)
    names = map_decls(lambda d: tuple(d.axes), registry.decls(cfg))
    seen_wo = False
    for ax, spec in _spec_leaves(cfg):
        dims = tuple(ax)
        if lx.HEADS in dims and dims[-1] != lx.HEAD_DIM:
            seen_wo = True
            assert spec == P(), (dims, spec)
    assert seen_wo, f"{arch}: no output projection found in {names}"
    del specs


@pytest.mark.parametrize("arch", PAGED)
def test_gqa_indivisible_tp_rejected(arch):
    """tp beyond the KV-head count (or not dividing it) fails loudly,
    naming the head count — never the silent-replication fallback."""
    cfg = configs.smoke(arch)
    bad = cfg.n_kv_heads * 2 - 1 if cfg.n_kv_heads > 1 else 2
    assert cfg.n_kv_heads % bad
    with pytest.raises(ValueError, match=r"n_kv_heads \d+ % tp"):
        R.validate_serve_tp(cfg, bad)


@pytest.mark.parametrize("arch", UNPAGED)
def test_lane_fallback_families_rejected(arch):
    """MoE / SSM / hybrid have no paged KV to shard — rejected at any tp
    with the family named, including tp=1 (the caller asked for the
    sharded path, not for a silent downgrade to lanes)."""
    cfg = configs.smoke(arch)
    for tp in (1, 2):
        with pytest.raises(ValueError, match="cannot serve tensor-parallel"):
            R.validate_serve_tp(cfg, tp)


def test_recurrent_state_cannot_shard_beyond_one():
    """A Mamba-2 hybrid serves on one device (tp=1) and is refused at any
    tp > 1, naming its state arena, though its KV heads would divide."""
    cfg = configs.smoke("granite_4_0_h_micro")
    assert registry.supports_paged(cfg) and registry.holds_state(cfg)
    R.validate_serve_tp(cfg, 1)
    for tp in (2, cfg.n_kv_heads):
        assert cfg.n_kv_heads % tp == 0
        with pytest.raises(ValueError, match="recurrent state arena"):
            R.validate_serve_tp(cfg, tp)


def test_mqa_cannot_shard_beyond_one():
    """A single shared KV head cannot split: the error says MQA, not just
    a bare modulus, so the operator knows it is architectural."""
    cfg = dataclasses.replace(configs.smoke("granite_3_2b"),
                              n_kv_heads=1, n_heads=4)
    R.validate_serve_tp(cfg, 1)             # fine on one device
    with pytest.raises(ValueError, match="MQA has a single shared KV head"):
        R.validate_serve_tp(cfg, 2)


def test_tp_below_one_rejected():
    cfg = configs.smoke("granite_3_2b")
    with pytest.raises(ValueError, match="tp must be >= 1"):
        R.validate_serve_tp(cfg, 0)


def test_serve_param_spec_head_dim_suffix_rule():
    """Unit coverage of the rule itself: only HEAD_DIM-suffixed axes with
    a head dim shard, the first head axis takes the mesh axis, trailing
    Nones are trimmed, and the tp axis name is a parameter."""
    assert R.serve_param_spec(Axes(lx.EMBED, lx.HEADS, lx.HEAD_DIM)) == \
        P(None, "model")
    assert R.serve_param_spec(Axes(lx.KV_HEADS, lx.HEAD_DIM)) == P("model")
    # wo: head axes but EMBED-suffixed -> replicated
    assert R.serve_param_spec(Axes(lx.HEADS, lx.HEAD_DIM, lx.EMBED)) == P()
    # no head axis at all -> replicated even when HEAD_DIM-suffixed
    assert R.serve_param_spec(Axes(lx.EMBED, lx.HEAD_DIM)) == P()
    assert R.serve_param_spec(Axes(lx.EMBED, lx.MLP)) == P()
    assert R.serve_param_spec(Axes()) == P()
    assert R.serve_param_spec(Axes(lx.EMBED, lx.HEADS, lx.HEAD_DIM),
                              tp_axis="tp") == P(None, "tp")


def test_pool_spec_and_shard_bytes():
    """The arena spec shards only the head-major lane dim; per-device bytes
    come out to 1/tp of the footprint for any divisible head count."""

    class _M:
        axis_names = ("model",)

        class devices:
            shape = (2,)

    spec = R.serve_pool_spec()
    assert spec == P(None, None, "model")
    # (L·P, page, Kh·Dh): 4 layers of 8 pages, 2 KV heads of 16 lanes
    full = R.shard_bytes((4 * 8, 8, 2 * 16), P(), _M, 4)
    half = R.shard_bytes((4 * 8, 8, 2 * 16), spec, _M, 4)
    assert full == 4 * 8 * 8 * 2 * 16 * 4
    # the leading (L·P, page) dims never split: pages stay device-invariant
    assert half * 2 == full
