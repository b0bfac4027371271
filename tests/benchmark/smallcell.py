"""A cell small enough for the CPU: the granite configuration at a
reduced width and depth, with the chat mix shortened to fit."""

import dataclasses
import time

from bench import harness, spec
from bench.record_trace import small_config

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def small_cell(name: str, loop: str = "open", limit: float | None = None):
    base = spec.cell("granite-3-2b.chat" if loop == "open"
                     else "granite-3-2b.decode")
    cfg = dict(small_config(base.config), name=name)
    mix = dict(base.traffic, check_tokens=120)
    mix["prompt"] = dict(mix["prompt"], median=40, sigma=0.8, min=8, max=48)
    mix["output"] = dict(mix["output"], median=24, sigma=0.6, min=4, max=48)
    if loop == "open":
        mix["rate_rps"] = 4.0
    return dataclasses.replace(base, config=cfg, traffic=mix,
                               limits={"max_logit_gap": limit})


def run(cell, seed=2**31 + 11, seconds=3.0, **kw):
    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            PEAKS, **kw)
