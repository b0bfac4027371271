"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The 512-host-device override lives in :func:`configure`, called by
:func:`main` before any jax device use — never at import time. (It used
to be a module-level ``os.environ`` write, which meant *importing* this
module for its constants — e.g. ``from repro.launch.dryrun import
RESULTS`` in benchmarks — silently clobbered the process's XLA flags.)
Tests and benchmarks see the real device set; only the dry-run CLI forces
512 hosts, and ``configure`` raises instead of silently no-opping when
jax has already locked its device count.

Usage:
  python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all          # every missing cell, in-process
  python -m repro.launch.dryrun --list         # show the cell matrix
Results land in results/dryrun/<arch>__<shape>__<mesh>.json (one file per
cell; reruns overwrite).
"""

import argparse
import os
import dataclasses
import json
import pathlib
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.analysis import hlo as hlo_lib
from repro.core.platform import Platform, XHeepConfig
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.serve.engine import build_sharded_serve
from repro.sharding import rules as R
from repro.train.trainer import TrainConfig, build_sharded_train

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def configure(devices: int) -> None:
    """Force ``devices`` host CPU devices for this process.

    Merges the override into any existing ``XLA_FLAGS`` (replacing a prior
    device-count flag, keeping everything else — the Makefile prepends an
    optimization-level flag that must survive). Must run before jax
    initializes its backends: the first device use locks the count, so if
    that already happened this raises instead of silently lowering every
    cell on the wrong mesh."""
    bridge = getattr(getattr(jax, "_src", None), "xla_bridge", None)
    if bridge is not None and getattr(bridge, "_backends", None):
        raise RuntimeError(
            f"jax already initialized its backends — the {devices}-host "
            "override must land before first device use (run the dry-run "
            "as its own process, not after other jax work)")
    flag = f"--xla_force_host_platform_device_count={devices}"
    kept = [f for f in os.environ.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    os.environ["XLA_FLAGS"] = " ".join(kept + [flag])

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, global_batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, global_batch=32),
    "decode_32k": dict(kind="decode", seq=32768, global_batch=128),
    "long_500k": dict(kind="decode", seq=524288, global_batch=1),
}

MESHES = {"single": dict(multi_pod=False, chips=256),
          "multi": dict(multi_pod=True, chips=512)}


def accum_for(cfg) -> int:
    # microbatch must stay divisible by the multi-pod batch axes (2*16=32)
    return 8  # global 256 -> microbatch 32


def optimizer_for(cfg) -> str:
    return "adafactor" if cfg.param_count() > 5e10 else "adamw"


def cell_enabled(cfg, shape_name: str) -> tuple[bool, str]:
    if shape_name == "long_500k" and not cfg.is_subquadratic:
        return False, "N/A: pure full attention (see DESIGN.md §Arch-applicability)"
    return True, ""


def build_platform(overrides: dict | None = None) -> Platform:
    return Platform(XHeepConfig(**(overrides or {})))


# --- §Perf hillclimb variants -------------------------------------------------
# Each variant: (cfg transform, platform kwargs, rule overrides, tc kwargs,
#                fsdp override). Lowered with --variant NAME; results are
# written under that tag and compared against `baseline` by benchmarks.roofline.
import dataclasses as _dc

VARIANTS: dict[str, dict] = {
    "baseline": {},
    # the paper-faithful minimal bus: pure DP, replicated weights
    "oat_bus": {"platform": {"bus": "one_at_a_time"}, "fsdp": False},
    # remat policy: keep matmul outputs, recompute elementwise only
    "remat_dots": {"cfg": lambda c: _dc.replace(c, remat="dots")},
    # pad vocab to a shardable multiple AND shard the (tied) embedding table's
    # vocab axis so head flops/bytes go tensor-parallel
    "vocab_pad": {"cfg": lambda c: _dc.replace(c, vocab_pad_multiple=2048),
                  "rules": {"vocab_in": ("model",)}},
    # force ZeRO-1 / full-FSDP regardless of the auto policy
    "zero1": {"fsdp": False},
    "fsdp": {"fsdp": True},
    # sequence parallelism on activations (interleaved addressing)
    "interleaved": {"platform": {"addressing": "interleaved"}},
    # accumulate more/fewer microbatches
    "accum16": {"accum": 16},
    "accum4": {"accum": 4},
    # MoE: bigger capacity (less dropping)
    "cap2x": {"cfg": lambda c: _dc.replace(c, moe_capacity_factor=2.5)},
    # SSD scan in bf16 (fp32 accumulation + state)
    "ssd_bf16": {"cfg": lambda c: _dc.replace(c, ssm_compute_dtype="bfloat16")},
    "mamba_combo": {"cfg": lambda c: _dc.replace(
        c, ssm_compute_dtype="bfloat16", vocab_pad_multiple=2048),
        "rules": {"vocab_in": ("model",)}},
    # expert parallelism on a reshaped single-pod mesh: 256 chips as
    # (data=32, model=8) so 8 experts shard over `model`; expert FFN d_ff
    # shards over `data` (no FSDP contraction over d_model -> no per-matmul
    # partial-sum all-reduce); embedding vocab FSDPs over data.
    "ep_mesh": {"mesh": (32, 8), "fsdp": False,
                "rules": {"expert": ("model",), "mlp": ("data",),
                          "embed": (), "vocab_in": ("data",)}},
    # combined winners (see EXPERIMENTS.md §Perf)
    "combo": {"cfg": lambda c: _dc.replace(c, remat="dots",
                                           vocab_pad_multiple=2048),
              "rules": {"vocab_in": ("model",)}},
    "combo_moe": {"mesh": (32, 8), "fsdp": False,
                  "cfg": lambda c: _dc.replace(c, remat="dots"),
                  "rules": {"expert": ("model",), "mlp": ("data",),
                            "embed": (), "vocab_in": ("data",)}},
    # G5: expert-parallel + capacity-dim data sharding; expert weights keep
    # d_model FSDP'd over data (fit), but the dispatch buffer's capacity dim
    # is constrained to `data` so FFN outputs stay small before reduction.
    "ep_cap": {"mesh": (32, 8), "fsdp": True,
               "rules": {"expert": ("model",), "mlp": (),
                         "vocab_in": ("data",)},
               "moe_dispatch_spec": ("model", "data", None)},
}


def run_cell(arch: str, shape_name: str, mesh_name: str,
             platform: Platform | None = None, tag: str = "baseline",
             rule_overrides: dict | None = None, verbose: bool = True,
             variant: str = "baseline") -> dict:
    cfg = configs.get(arch)
    spec = SHAPES[shape_name]
    var = VARIANTS[variant]
    if "cfg" in var:
        cfg = var["cfg"](cfg)
    if "platform" in var and platform is None:
        platform = build_platform(var["platform"])
    fsdp_override = var.get("fsdp")
    accum_override = var.get("accum")
    from jax.sharding import PartitionSpec as _PS

    from repro.models import layers as _layers

    _layers.set_moe_dispatch_spec(
        _PS(*var["moe_dispatch_spec"]) if "moe_dispatch_spec" in var else None)
    ok, why = cell_enabled(cfg, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": why}

    platform = platform or build_platform()
    if "mesh" in var and mesh_name == "single":
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(var["mesh"], ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=MESHES[mesh_name]["multi_pod"])
    chips = MESHES[mesh_name]["chips"]
    rules = platform.rules(mesh)
    overrides = dict(var.get("rules", {}))
    if rule_overrides:
        overrides.update(rule_overrides)
    if overrides:
        rules = rules.override(name=f"{rules.name}+{variant}", **overrides)

    from repro.analysis.jaxpr_cost import loop_correction

    t0 = time.time()
    if spec["kind"] == "train":
        accum = accum_override or accum_for(cfg)
        tc = TrainConfig(optimizer=optimizer_for(cfg), accum=accum,
                         accum_dtype="bfloat16" if cfg.param_count() > 1e11
                         else "float32")
        st = build_sharded_train(cfg, tc, mesh, rules,
                                 spec["global_batch"], spec["seq"],
                                 fsdp=fsdp_override)
        corr_args = (st.raw_fn, st.params_abstract, st.opt_abstract,
                     st.batch_abstract)
        with mesh:
            lowered = st.step_fn.lower(st.params_abstract, st.opt_abstract,
                                       st.batch_abstract)
    else:
        sv = build_sharded_serve(cfg, mesh, rules, spec["global_batch"],
                                 spec["seq"],
                                 prefill_len=spec["seq"] if spec["kind"] == "prefill"
                                 else None,
                                 fsdp=fsdp_override)
        with mesh:
            if spec["kind"] == "prefill":
                p_in = sv.prefill_fn._input_abstract
                corr_args = (sv.raw_prefill_fn, sv.params_abstract, p_in)
                lowered = sv.prefill_fn.lower(sv.params_abstract, p_in)
            else:
                tok = jax.ShapeDtypeStruct((spec["global_batch"], 1), jnp.int32)
                corr_args = (sv.raw_decode_fn, sv.params_abstract,
                             sv.cache_abstract, tok)
                lowered = sv.decode_fn.lower(sv.params_abstract, sv.cache_abstract,
                                             tok)
    t_lower = time.time() - t0

    # Loop-trip-count correction ratios (XLA counts while bodies once).
    with mesh:
        fr, br, full_cost = loop_correction(*corr_args)

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_stats = {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "peak_bytes_est": mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes,
    }
    xla_cost = dict(compiled.cost_analysis() or {})
    txt = compiled.as_text()
    cost = hlo_lib.analyze(txt, chips)
    model_flops = hlo_lib.model_flops_for(cfg, spec["kind"],
                                          spec["global_batch"], spec["seq"])
    roof = hlo_lib.make_roofline(arch, shape_name, mesh_name, chips,
                                 cost, model_flops, mem_stats)
    out = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
        "status": "ok", "kind": spec["kind"],
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "hlo_bytes_text": len(txt),
        "rules": rules.name,
        "xla_raw_flops": float(xla_cost.get("flops", 0.0)),
        "xla_raw_bytes": float(xla_cost.get("bytes accessed", 0.0)),
        "jaxpr_flops_global": full_cost.flops,
        "jaxpr_bytes_global": full_cost.bytes,
        **roof.to_dict(),
    }
    if verbose:
        gb = 1024 ** 3
        print(f"[{arch} × {shape_name} × {mesh_name}] ({tag})")
        print(f"  memory/device: args {mem_stats['argument_bytes']/gb:.2f} GiB, "
              f"temp {mem_stats['temp_bytes']/gb:.2f} GiB, "
              f"peak≈{mem_stats['peak_bytes_est']/gb:.2f} GiB "
              f"(HBM {hlo_lib.hw.TPU_V5E.hbm_bytes/gb:.0f} GiB)")
        print(f"  flops/device {roof.flops_per_device:.3e}, hbm bytes "
              f"{roof.hbm_bytes_per_device:.3e}, wire bytes {roof.wire_bytes_per_device:.3e}")
        print(f"  roofline terms (s): compute {roof.compute_s:.4f}, memory "
              f"{roof.memory_s:.4f}, collective {roof.collective_s:.4f} "
              f"-> dominant: {roof.dominant}")
        print(f"  collectives: {cost.collective_counts}")
        print(f"  MODEL_FLOPS/HLO_FLOPS {roof.useful_flops_ratio:.3f}, "
              f"roofline fraction {roof.roofline_fraction:.3f}")
        print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s")
    return out


def cell_path(arch: str, shape: str, mesh: str, tag: str = "baseline") -> pathlib.Path:
    suffix = "" if tag == "baseline" else f"__{tag}"
    return RESULTS / f"{arch}__{shape}__{mesh}{suffix}.json"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=list(MESHES))
    ap.add_argument("--tag", default=None)
    ap.add_argument("--variant", default="baseline", choices=list(VARIANTS))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)
    if args.tag is None:
        args.tag = args.variant

    cells = []
    for arch in configs.names():
        aid = configs.get(arch).name
        for shape in SHAPES:
            for mesh in MESHES:
                cells.append((aid, shape, mesh))

    if args.list:
        for c in cells:
            p = cell_path(*c)
            print(("done " if p.exists() else "todo "), *c)
        return 0

    if not args.all:
        assert args.arch, "--arch required unless --all/--list"
        cells = [(args.arch, args.shape or "train_4k", args.mesh)]

    # only lowering runs touch device state or create the artifact dir —
    # `--list` must stay side-effect-free so the artifact-gated tests keep
    # skipping (and so listing never demands an uninitialized jax)
    configure(512)
    RESULTS.mkdir(parents=True, exist_ok=True)
    failures = 0
    for arch, shape, mesh in cells:
        path = cell_path(arch, shape, mesh, args.tag)
        if path.exists() and not args.force:
            continue
        try:
            out = run_cell(arch, shape, mesh, tag=args.tag,
                           variant=args.variant)
        except Exception:  # record the failure, keep going
            traceback.print_exc()
            out = {"arch": arch, "shape": shape, "mesh": mesh, "tag": args.tag,
                   "status": "error", "error": traceback.format_exc(limit=20)}
            failures += 1
        path.write_text(json.dumps(out, indent=1))
        sys.stdout.flush()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
