"""Model step: median host time of one ``engine.step()`` in the window
(it waits for the device when it retires the step's tokens)."""

import statistics


def read(run, variant):
    steps = run.window_steps()
    return (statistics.median((s.end - s.start) * 1e3 for s in steps)
            if steps else None)
