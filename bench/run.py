"""Run one benchmark cell once on the chip this process finds.

  python3 bench/run.py --workload granite-3-2b.chat --seed 7 --seconds 45 --trace 0

The cell, its configuration, traffic, limit and metrics are found by name
(``bench/spec.py``). The run makes its weights and traffic from
``--seed``, warms up every program the window drives (set-up), measures
for ``--seconds``, then checks the served tokens against the float32
reference. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces part of the window and reports its per-layer
metrics. The last line of standard output is one JSON object; the last
lines of standard error are the numbers compared, each with its limit.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)


def enable_cache() -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, for
    every program however quick it compiles."""
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def open_chip(what: str, chips: int = 1):
    """The first ``chips`` TPU devices, with the compile cache on; None,
    after saying why on standard error, where JAX finds fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: {what} needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s). Nothing was "
              "run.", file=sys.stderr)
        return None
    enable_cache()
    return devices[:chips]


def metrics_of(run, metrics) -> dict:
    from bench import spec

    out = {}
    for m in metrics:
        value = spec.reader(m.name)(run)
        if value is not None:
            out[m.name] = {"value": value, "unit": m.unit}
    return out


def result_line(run, outcome, trace: bool, device: dict) -> dict:
    cell = run.cell
    out = {"correct": outcome["correct"], "attempted": outcome["attempted"],
           "failed": outcome["failed"],
           "metrics": metrics_of(run, cell.per_layer if trace
                                 else cell.end_to_end),
           "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = outcome["checks"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from bench import harness, spec

    cell = spec.cell(args.workload)
    used = open_chip(args.workload, cell.chips)
    if used is None:
        return 2
    tag = f"[{used[0].platform} {used[0].device_kind} x{len(used)}]"

    def log(msg):
        print(tag, msg, flush=True)

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: compiles.append((time.perf_counter(), name))
        if name in COMPILE_EVENTS else None)
    peaks = harness.device_peaks()
    run, outcome = harness.run_cell(cell, args.seed, args.seconds,
                                    bool(args.trace), T_START, peaks, log)
    in_window = sum(run.t0 <= t <= run.t1 for t, _ in compiles)
    log(f"setup {run.setup_s:.3f} s; programs compiled in set-up "
        f"{sum(t < run.t0 for t, _ in compiles)}, in the window {in_window}")
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used),
              "memory_peak_bytes": outcome["memory_peak_bytes"]}
    line = result_line(run, outcome, bool(args.trace), device)
    for name, c in outcome["checks"].items():
        rule = ">=" if name == "tokens_compared" else "<="
        print(f"{tag} check {name} {c['value']} (limit {rule} {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
