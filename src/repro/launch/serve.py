"""Serving driver: batched prefill + decode with throughput/energy report.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --smoke \
      --batch 4 --prompt-len 32 --steps 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import energy
from repro.core.platform import Platform, XHeepConfig
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import registry
from repro.serve.engine import build_sharded_serve
from repro.sharding import params as P


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    platform = Platform(XHeepConfig())
    mesh = make_production_mesh() if args.production_mesh else make_host_mesh()
    rules = platform.rules(mesh)
    max_len = args.max_len or (args.prompt_len + args.steps)

    sv = build_sharded_serve(cfg, mesh, rules, args.batch, max_len,
                             prefill_len=args.prompt_len)
    key = jax.random.key(args.seed)
    params = P.init_tree(registry.decls(cfg), key, dtype=jnp.bfloat16)

    done = {"flag": False}

    def on_complete(_):
        done["flag"] = True   # XAIF-style completion interrupt

    with mesh:
        if cfg.embed_inputs:
            prompt = jax.random.randint(key, (args.batch, args.prompt_len),
                                        0, cfg.vocab)
            logits, cache = sv.prefill_fn(params, prompt)
        else:
            emb = jax.random.normal(
                key, (args.batch, args.prompt_len, cfg.d_model), jnp.bfloat16)
            logits, cache = sv.prefill_fn(params, emb)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        t0 = time.time()
        outs = []
        for _ in range(args.steps):
            outs.append(tok)
            logits, cache = sv.decode_fn(params, cache, tok)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        jax.block_until_ready(logits)
        dt = time.time() - t0
        on_complete(outs)

    toks = args.batch * args.steps
    n = cfg.param_count()
    e_j = energy.tpu_step_energy_j(flops=2 * n * toks, hbm_bytes=2 * n * 2,
                                   step_s=dt, chips=len(jax.devices()))
    print(f"decoded {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s); est energy {e_j:.1f} J "
          f"({e_j / max(toks, 1) * 1000:.1f} mJ/token)")
    assert done["flag"], "completion interrupt not fired"
    return toks / dt


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
