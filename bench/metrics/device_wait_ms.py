"""Model step: median time a window step waits for its tokens
(``engine.wait`` in the program's step log, ``bench/spans.py``): the
step's device time less the host work that runs under it after the
launch (``engine.account``). The inside twin of ``step_ms``."""

import statistics

from bench import spans


def read(run, variant):
    steps = spans.window(run)
    if steps is None:
        return None
    waits = [t.phases["engine.wait"] * 1e3 for t in steps
             if "engine.wait" in t.phases]
    return statistics.median(waits) if waits else None
