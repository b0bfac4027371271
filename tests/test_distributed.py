"""Multi-device subprocess tests: sharded lowering, compressed pod psum,
pipeline parallelism, production-mesh smoke (tiny arch on 512 devices)."""

import pytest

pytestmark = pytest.mark.slow   # every test here forks a multi-device process


def test_compressed_pod_psum_close_to_exact(subproc):
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.train.compress import compressed_psum_tree, init_error_state

mesh = make_mesh((2, 2), ("pod", "data"))
g = {"w": jax.random.normal(jax.random.key(0), (2, 64)) * 1e-2}
err = init_error_state(g, jnp.float32)

def inner(g, e):
    return compressed_psum_tree(g, e, "pod")

out, new_err = jax.shard_map(inner, mesh=mesh,
                             in_specs=(P("pod"), P("pod")),
                             out_specs=(P("pod"), P("pod")),
                             check_vma=False)(g, err)
exact = jnp.mean(g["w"], axis=0, keepdims=True).repeat(2, 0)
rel = float(jnp.abs(out["w"] - exact).max() / jnp.abs(exact).max())
assert rel < 0.02, rel          # int8: ~1% worst-case per-tensor error
assert float(jnp.abs(new_err["w"]).max()) > 0  # error feedback captured
print("COMPRESS_OK", rel)
"""
    assert "COMPRESS_OK" in subproc(code, devices=4)


def test_pipeline_forward_matches_sequential(subproc):
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.train.pipeline import pipeline_forward

S, M, D = 4, 6, 8
mesh = make_mesh((S,), ("stage",))
key = jax.random.key(0)
ws = jax.random.normal(key, (S, D, D)) * 0.3

def stage_fn(w, x):
    return jnp.tanh(x @ w)

xs = jax.random.normal(jax.random.key(1), (M, 2, D))
run = pipeline_forward(stage_fn, mesh, "stage")
got = run(ws, xs)

want = xs
for i in range(S):
    want = jnp.tanh(want @ ws[i])
np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
print("PIPELINE_OK")
"""
    assert "PIPELINE_OK" in subproc(code, devices=4)


def test_tiny_arch_runs_on_production_mesh(subproc):
    """Numerically run (not just compile) a smoke arch on the 16x16 mesh."""
    code = """
import jax, jax.numpy as jnp
from repro import configs
from repro.core.platform import Platform, XHeepConfig
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.sharding import params as P
from repro.train.trainer import TrainConfig, build_sharded_train
from repro.train import optim as optim_lib

cfg = configs.smoke("granite_3_2b")
mesh = make_production_mesh()            # 16 x 16 = 256 host devices
platform = Platform(XHeepConfig())
rules = platform.rules(mesh)
tc = TrainConfig(optimizer="adamw", accum=2)
st = build_sharded_train(cfg, tc, mesh, rules, global_batch=32, seq=32)
params = P.init_tree(registry.decls(cfg), jax.random.key(0), dtype=jnp.bfloat16)
opt = optim_lib.get("adamw").init(params)
key = jax.random.key(1)
batch = {"tokens": jax.random.randint(key, (2, 16, 32), 0, cfg.vocab),
         "labels": jax.random.randint(key, (2, 16, 32), 0, cfg.vocab)}
batch = jax.tree.map(jax.device_put, batch, st.batch_shardings)
with mesh:
    params, opt, metrics = st.step_fn(params, opt, batch)
loss = float(metrics["loss"])
assert jnp.isfinite(loss), loss
print("PRODMESH_OK", loss)
"""
    assert "PRODMESH_OK" in subproc(code, devices=256, timeout=560)


def test_multipod_serve_lowering(subproc):
    code = """
import jax, jax.numpy as jnp
from repro import configs
from repro.core.platform import Platform, XHeepConfig
from repro.launch.mesh import make_production_mesh
from repro.serve.engine import build_sharded_serve

cfg = configs.get("recurrentgemma-2b")
mesh = make_production_mesh(multi_pod=True)   # (2,16,16) = 512
rules = Platform(XHeepConfig()).rules(mesh)
sv = build_sharded_serve(cfg, mesh, rules, batch=128, max_len=32768)
tok = jax.ShapeDtypeStruct((128, 1), jnp.int32)
with mesh:
    compiled = sv.decode_fn.lower(sv.params_abstract, sv.cache_abstract, tok).compile()
mem = compiled.memory_analysis()
assert mem.argument_size_in_bytes > 0
print("MULTIPOD_OK", mem.argument_size_in_bytes)
"""
    assert "MULTIPOD_OK" in subproc(code, devices=512, timeout=560)
