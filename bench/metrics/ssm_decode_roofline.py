"""Kernel: the Mamba-2 decode kernel's share of its roofline, in percent.

The least time the chip needs for the kernel's work in the traced steps
— the larger of its FLOPs over the bf16 peak and its needed bytes over
HBM bandwidth (``bench/kernels/ssm_decode.py``), for each token fed —
over the kernel's device time in the trace. Nothing without a trace; an
error where the trace holds device ops but none by the kernel's name,
since every step of a cell it applies to runs the kernel."""

from bench.kernels import ssm_decode


def read(run, variant):
    if run.trace is None or run.traced is None:
        return None
    steps = run.window_steps(run.traced)
    if not steps:
        return None
    device_s = run.trace.seconds_of(ssm_decode.TRACE_NAMES)
    if device_s <= 0:
        raise LookupError(
            f"the trace's device ops hold none named {ssm_decode.TRACE_NAMES}"
            f"; the busiest are {run.trace.breakdown(5)['device_ops']}")
    flops = bytes_ = 0.0
    for s in steps:
        f, b = ssm_decode.cost(run.cfg, s.fed)
        flops += f
        bytes_ += b
    need = max(flops / run.peaks["bf16_flops_per_s"],
               bytes_ / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / device_s
