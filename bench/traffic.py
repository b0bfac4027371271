"""One general generator for every traffic mix under ``bench/traffic/``.

A mix file gives the loop (``open``: arrivals on a schedule; ``backlog``:
a queue kept full), the arrival process and rate, and the distribution
and clip of prompt and output lengths. What a seed changes is the order
and the token ids, not the work: lengths and gaps are a stratified set —
the quantiles ``(i + 0.5) / n`` of the stated distribution — that each
seed permutes. So two seeds offer the same multiset of lengths and the
same gaps, and their runs differ by arrangement alone.

The arrival processes are those of ``src/repro/serve/loadgen.py``:
Poisson (exponential gaps) and compound-Poisson bursts (events at
``rate / burst``, each releasing 1 to ``2·burst − 1`` requests at once),
drawn here as stratified sets instead of i.i.d. streams.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Arrival:
    due: float          # seconds after the window opens (open loop)
    prompt_len: int
    output_len: int


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The ``n`` stratified lengths of ``dist``, clipped, in rising order."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([_NORMAL.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)


def _exp_gaps(rate: float, n: int) -> np.ndarray:
    return -np.log1p(-(np.arange(n) + 0.5) / n) / rate


def arrival_times(spec: dict, seconds: float, rng: np.random.Generator
                  ) -> np.ndarray:
    """Due times in ``[0, seconds)`` of an open-loop mix."""
    rate = spec["rate_rps"]
    if spec["arrivals"] == "poisson":
        gaps = rng.permutation(_exp_gaps(rate, max(1, round(rate * seconds))))
        times = np.cumsum(gaps)
    elif spec["arrivals"] == "bursty":
        burst = spec["burst"]
        n_events = max(1, round(rate * seconds / burst))
        gaps = rng.permutation(_exp_gaps(rate / burst, n_events))
        sizes = rng.permutation(
            1 + np.arange(n_events) % (2 * burst - 1))
        times = np.repeat(np.cumsum(gaps), sizes)
    else:
        raise ValueError(f"unknown arrival process {spec['arrivals']!r}")
    return times[times < seconds]


def open_loop(spec: dict, seconds: float, rng: np.random.Generator
              ) -> list[Arrival]:
    """Every request due in a window of ``seconds``, in due order."""
    times = arrival_times(spec, seconds, rng)
    n = len(times)
    prompts = rng.permutation(quantiles(spec["prompt"], n))
    outputs = rng.permutation(quantiles(spec["output"], n))
    return [Arrival(float(t), int(p), int(o))
            for t, p, o in zip(times, prompts, outputs)]


def backlog(spec: dict, n: int, rng: np.random.Generator) -> list[Arrival]:
    """``n`` requests for a queue kept full. Each run of ``block``
    consecutive requests is a whole stratified set, so whatever prefix of
    the backlog a window consumes, its lengths are the same for every
    seed to within one block."""
    block = spec["block"]
    prompts = quantiles(spec["prompt"], block)
    outputs = quantiles(spec["output"], block)
    out = []
    while len(out) < n:
        out += [Arrival(0.0, int(p), int(o)) for p, o in
                zip(rng.permutation(prompts), rng.permutation(outputs))]
    return out[:n]
