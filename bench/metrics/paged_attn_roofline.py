"""Kernel: the paged-attention kernel's share of its roofline, in percent.

The least time the chip needs for the kernel's work in the traced steps
— the larger of its FLOPs over the bf16 peak and its needed bytes over
HBM bandwidth (``bench/kernels/paged_attn.py``) — over the kernel's
device time in the trace. Nothing without a trace; an error where the
trace holds device ops but none by the kernel's name, since every cell's
step runs the kernel and a silent gap would hide a renamed kernel."""

from bench.kernels import paged_attn


def read(run, variant):
    if run.trace is None or run.traced is None:
        return None
    steps = run.window_steps(run.traced)
    if not steps:
        return None
    device_s = run.trace.seconds_of(paged_attn.TRACE_NAMES)
    if device_s <= 0:
        raise LookupError(
            f"the trace's device ops hold none named {paged_attn.TRACE_NAMES}"
            f"; the busiest are {run.trace.breakdown(5)['device_ops']}")
    flops = bytes_ = 0.0
    for s in steps:
        f, b = paged_attn.cost(run.cfg, s.fed, s.attended)
        flops += f
        bytes_ += b
    need = max(flops / run.peaks["bf16_flops_per_s"],
               bytes_ / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / device_s
