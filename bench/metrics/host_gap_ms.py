"""Engine: median time in which the device has no step queued, from the
program's step log (``bench/spans.py``): for consecutive steps N, N+1 of
the window, the end of N's wait for its tokens to the return of N+1's
launch. The inside account of ``device_idle_share``."""

import statistics

from bench import spans


def read(run, variant):
    steps = spans.window(run)
    if steps is None:
        return None
    gaps = [(b.launched - a.waited) * 1e3 for a, b in zip(steps, steps[1:])
            if a.waited is not None and b.launched is not None]
    return statistics.median(gaps) if gaps else None
