"""Quickstart: configure an X-HEEP platform, train a small LM, serve it.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro import configs
from repro.core.platform import Platform, XHeepConfig
from repro.core.power import PowerState
from repro.data.lm import LMDataConfig, LMPipeline
from repro.launch.mesh import make_host_mesh
from repro.models import registry
from repro.sharding import params as P
from repro.train import optim as optim_lib
from repro.train.trainer import TrainConfig, build_sharded_train


def main():
    # 1. Configure the platform (the paper's §III configurability axes):
    #    core choice = execution backend, bus topology = sharding rules.
    platform = Platform(XHeepConfig(core="cv32e40x", bus="fully_connected",
                                    addressing="contiguous", n_banks=8))
    mesh = make_host_mesh()
    rules = platform.rules(mesh)
    print("platform:", platform.config)
    print("rules preset:", rules.name)

    # 2. Pick an architecture (reduced config for CPU) and build training.
    cfg = configs.smoke("granite_3_2b")
    tc = TrainConfig(optimizer="adamw", lr=2e-3, accum=2)
    st = build_sharded_train(cfg, tc, mesh, rules, global_batch=8, seq=64)
    params = P.init_tree(registry.decls(cfg), jax.random.key(0),
                         dtype=jnp.bfloat16)
    opt_state = optim_lib.get(tc.optimizer).init(params)
    data = LMPipeline(LMDataConfig(vocab=cfg.vocab, seq=64, global_batch=8,
                                   accum=2))

    # 3. Train a few steps.
    with mesh:
        for step in range(10):
            params, opt_state, metrics = st.step_fn(params, opt_state,
                                                    data.batch_at(step))
            print(f"step {step}: loss {float(metrics['loss']):.4f}")

    # 4. Power-gate what we are not using (the paper's §III-A5 mechanism).
    platform.power.set_state("bank7", PowerState.OFF)
    platform.power.set_state("bank6", PowerState.RETENTION)
    print("power states:", {k: v.value for k, v in platform.power.states.items()})

    # 5. Serve a few greedy tokens from the trained weights.
    cache = registry.cache_init(cfg, batch=2, max_len=32)
    tok = jnp.zeros((2, 1), jnp.int32)
    outs = []
    for _ in range(8):
        logits, cache = registry.decode_step(params, cfg, cache, tok)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        outs.append(int(tok[0, 0]))
    print("greedy tokens:", outs)


if __name__ == "__main__":
    main()
