"""End-to-end training driver (CPU-scale runnable; pod-scale by mesh flag).

Wires every substrate together: platform config -> rules -> sharded train
step -> step-indexed data pipeline -> checkpoint/restart -> FT controller.

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b --smoke \
      --steps 20 --global-batch 8 --seq 128 --ckpt /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.ckpt import checkpoint
from repro.core.platform import Platform, XHeepConfig
from repro.data.lm import LMDataConfig, LMPipeline
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import registry
from repro.runtime.ft import FTController
from repro.sharding import params as P
from repro.train.trainer import TrainConfig, build_sharded_train


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor", "lion"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 mesh (requires 256 devices)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    platform = Platform(XHeepConfig())
    mesh = make_production_mesh() if args.production_mesh else make_host_mesh()
    rules = platform.rules(mesh)
    tc = TrainConfig(optimizer=args.optimizer, lr=args.lr, accum=args.accum)

    st = build_sharded_train(cfg, tc, mesh, rules, args.global_batch, args.seq)

    data = LMPipeline(LMDataConfig(
        vocab=cfg.vocab, seq=args.seq, global_batch=args.global_batch,
        accum=args.accum, seed=args.seed,
        embed_dim=None if cfg.embed_inputs else cfg.d_model))

    # init or restore
    decls = registry.decls(cfg)
    start_step = 0
    if args.resume and args.ckpt and checkpoint.latest_step(args.ckpt) is not None:
        params_like = st.params_abstract
        opt_like = st.opt_abstract
        params, start_step, _ = checkpoint.restore(
            args.ckpt, params_like, shardings=st.params_shardings)
        opt_state, _, _ = checkpoint.restore(
            args.ckpt + "/opt", opt_like, shardings=st.opt_shardings)
        print(f"resumed from step {start_step}")
    else:
        key = jax.random.key(args.seed)
        params = P.init_tree(
            decls, key,
            dtype=jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
        from repro.train import optim as optim_lib

        opt_state = optim_lib.get(tc.optimizer).init(params)

    ft = FTController(n_workers=jax.process_count())
    pending_save = None
    loss = float("nan")
    with mesh:
        for step in range(start_step, args.steps):
            t0 = time.time()
            batch = data.batch_at(step)
            params, opt_state, metrics = st.step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            ft.report_heartbeat(jax.process_index())
            ft.report_step_time(jax.process_index(), dt)
            ft.tick()
            print(f"step {step:5d} loss {loss:.4f} ({dt:.2f}s)", flush=True)
            if args.ckpt and (step + 1) % args.ckpt_every == 0:
                if pending_save is not None:
                    pending_save.join()
                pending_save = checkpoint.save(
                    args.ckpt, params, step=step + 1, async_=True,
                    metadata={"arch": cfg.name})
                checkpoint.save(args.ckpt + "/opt", opt_state, step=step + 1)
    if pending_save is not None:
        pending_save.join()
    print("done; final loss", loss)
    return loss


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
