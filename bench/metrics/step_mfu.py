"""Model step: model FLOPs of the tokens fed in the traced stretch of the
window, over its seconds times the chip's bf16 peak, in percent."""

from bench.flops import tokens_flops


def read(run, variant):
    if run.traced is None:
        return None
    steps = run.window_steps(run.traced)
    if not steps:
        return None
    flops = sum(tokens_flops(run.cfg, s.fed, s.attended) for s in steps)
    seconds = steps[-1].end - steps[0].start
    return 100.0 * flops / (seconds * run.peaks["bf16_flops_per_s"])
