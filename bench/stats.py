"""Arithmetic shared by the metric readers.

``percentile`` is the nearest-rank percentile of
``src/repro/serve/metrics.py`` (no interpolation: the value reported is
one that some request saw); ``ttft_ms`` and ``gaps_ms`` read the host
stamps the harness took.
"""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest rank: the smallest value with at least ``q`` percent of the
    data at or below it."""
    if not values:
        raise ValueError("percentile of an empty sequence")
    if not 0 < q <= 100:
        raise ValueError(f"q must be in (0, 100], got {q}")
    xs = sorted(values)
    return float(xs[max(0, math.ceil(q / 100 * len(xs)) - 1)])


def ttft_ms(run) -> list[float]:
    """Due time to first token, of every request due in the window that
    got one."""
    return [(r.stamps[0] - r.due) * 1e3 for r in run.reqs
            if r.in_window and r.stamps]


def gaps_ms(run) -> list[float]:
    """Every gap between consecutive tokens of one request that opens
    inside the window (so a stall at its end still counts)."""
    return [(b - a) * 1e3 for r in run.reqs
            for a, b in zip(r.stamps, r.stamps[1:]) if run.t0 <= a <= run.t1]


def tokens_in(run, span) -> int:
    a, b = span
    return sum(a <= t <= b for r in run.reqs for t in r.stamps)
