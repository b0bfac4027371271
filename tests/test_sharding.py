"""Rule-engine unit + property tests (hypothesis)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec

from repro.launch.mesh import make_host_mesh
from repro.sharding import axes as lx
from repro.sharding import rules as R
from repro.sharding.params import Axes, ParamDecl, axes_tree, init_tree, stack_tree


class FakeMesh:
    """Mesh stand-in with arbitrary axis sizes (no devices needed)."""

    def __init__(self, sizes: dict):
        self.axis_names = tuple(sizes)

        class _Dev:
            shape = tuple(sizes.values())

        self.devices = _Dev()


def fake_mesh(sizes):
    return FakeMesh(sizes)


PROD_MESH = fake_mesh({"data": 16, "model": 16})
POD_MESH = fake_mesh({"pod": 2, "data": 16, "model": 16})


def fc(mesh):
    return R.fully_connected(mesh)


def test_divisibility_fallback():
    rules = fc(PROD_MESH)
    # kv_heads=8 does not divide model=16 -> replicated; embed FSDPs on data
    spec = R.spec_for((2048, 8, 64), (lx.EMBED, lx.KV_HEADS, lx.HEAD_DIM),
                      rules, PROD_MESH)
    assert spec == PartitionSpec("data")
    # heads=32 divides -> sharded
    spec = R.spec_for((2048, 32, 64), (lx.EMBED, lx.HEADS, lx.HEAD_DIM),
                      rules, PROD_MESH)
    assert spec == PartitionSpec("data", "model")
    # odd dim (49155 vocab) -> replicated
    spec = R.spec_for((49155, 64), (lx.VOCAB, lx.HEAD_DIM), rules, PROD_MESH)
    assert spec == PartitionSpec()


def test_no_duplicate_mesh_axes():
    rules = fc(POD_MESH)
    # batch takes (pod, data); embed wants data -> must NOT reuse it
    spec = R.spec_for((256, 4096, 2048), (lx.BATCH, lx.SEQ, lx.EMBED),
                      rules, POD_MESH)
    flat = [a for e in spec for a in ((e,) if isinstance(e, str) else (e or ()))]
    assert len(flat) == len(set(flat))
    assert spec[0] == ("pod", "data")


def test_one_at_a_time_is_pure_dp():
    rules = R.one_at_a_time(PROD_MESH)
    spec = R.spec_for((1024, 1024), (lx.EMBED, lx.MLP), rules, PROD_MESH)
    assert spec == PartitionSpec()


@settings(max_examples=200, deadline=None)
@given(
    dims=st.lists(st.sampled_from(
        [lx.BATCH, lx.SEQ, lx.EMBED, lx.MLP, lx.HEADS, lx.KV_HEADS,
         lx.VOCAB, lx.EXPERT, lx.HEAD_DIM, None]), min_size=1, max_size=5),
    sizes=st.lists(st.integers(1, 4096), min_size=5, max_size=5),
    pod=st.booleans(),
)
def test_spec_property_valid_and_divisible(dims, sizes, pod):
    mesh = POD_MESH if pod else PROD_MESH
    rules = fc(mesh)
    shape = tuple(sizes[:len(dims)])
    spec = R.spec_for(shape, tuple(dims), rules, mesh)
    msizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used = []
    for dim_size, entry in zip(shape, tuple(spec) + (None,) * len(shape)):
        names = (entry,) if isinstance(entry, str) else (entry or ())
        prod = 1
        for nm in names:
            used.append(nm)
            prod *= msizes[nm]
        # property 1: every sharded dim is exactly divisible
        assert dim_size % prod == 0
    # property 2: no mesh axis used twice
    assert len(used) == len(set(used))


@settings(max_examples=50, deadline=None)
@given(n_layers=st.integers(1, 8), d=st.integers(1, 64))
def test_stack_tree_prepends_layer_axis(n_layers, d):
    decl = ParamDecl((d, d * 2), Axes(lx.EMBED, lx.MLP), init="fan_in")
    stacked = stack_tree({"w": decl}, n_layers, lx.LAYERS)
    assert stacked["w"].shape == (n_layers, d, d * 2)
    assert tuple(stacked["w"].axes) == (lx.LAYERS, lx.EMBED, lx.MLP)


def test_init_tree_deterministic_and_independent():
    decls = {"a": ParamDecl((4, 8), Axes(None, None)),
             "b": ParamDecl((4, 8), Axes(None, None))}
    t1 = init_tree(decls, jax.random.key(0))
    t2 = init_tree(decls, jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(t1["a"]), np.asarray(t2["a"]))
    assert np.abs(np.asarray(t1["a"]) - np.asarray(t1["b"])).max() > 1e-6


def test_init_tree_in_bf16_is_the_cast_float32_tree():
    """One seed gives one bf16 tree: drawing in bf16 equals casting the
    float32 draw, bit for bit, and each leaf equals its eager draw."""
    decls = {"normal": ParamDecl((64, 96), Axes(None, None), scale=0.02),
             "fan_in": ParamDecl((3, 96, 64), Axes(None, None, None),
                                 init="fan_in"),
             "ones": ParamDecl((96,), Axes(None), init="ones")}
    f32 = init_tree(decls, jax.random.key(5))
    b16 = init_tree(decls, jax.random.key(5), dtype=jnp.bfloat16)
    keys = jax.random.split(jax.random.key(5), len(decls))
    for (name, decl), key in zip(sorted(decls.items()), keys):
        want = np.asarray(decl.materialize(key))
        np.testing.assert_array_equal(np.asarray(f32[name]), want)
        assert b16[name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(b16[name]), np.asarray(f32[name].astype(jnp.bfloat16)))


def test_shard_bytes():
    rules = fc(PROD_MESH)
    spec = R.spec_for((1024, 4096), (lx.EMBED, lx.MLP), rules, PROD_MESH)
    assert spec == PartitionSpec("data", "model")  # FSDP x TP
    b = R.shard_bytes((1024, 4096), spec, PROD_MESH, 2)
    assert b == 1024 * 4096 * 2 // (16 * 16)


def test_interleaved_addressing_adds_sequence_parallelism():
    from repro.core.platform import Platform, XHeepConfig

    mesh = make_host_mesh()
    p_cont = Platform(XHeepConfig(addressing="contiguous"))
    p_int = Platform(XHeepConfig(addressing="interleaved"))
    assert p_cont.rules(mesh).lookup(lx.SEQ) == ()
    assert p_int.rules(mesh).lookup(lx.SEQ) == ("data",)
