"""The program's own step log, cut to a run's window.

The engine logs one record per working step (``repro.serve.tracing``):
its phases' seconds from host spans, the times at which its launch
returned and its wait for the device ended, and counters. Program and
harness both stamp on ``time.perf_counter``, so a record belongs to the
window when it starts at or after ``run.t0`` and ends at or before
``run.t1``; the readers cover the whole window of the run, as
``queue_wait_ms`` does.

A program that keeps no such log gives None. A log that has already
dropped the window's first step raises, so that no reader reports on a
truncated window.
"""

from __future__ import annotations


def window(run) -> list | None:
    """The engine's step records of ``run``'s window, oldest first; None
    where the program logged none there."""
    try:
        from bench import sut  # noqa: F401  (puts the program on the path)
        from repro.serve import tracing
    except ImportError:
        return None
    kept = tracing.steps()
    records = [t for t in kept if t.start >= run.t0 and t.end <= run.t1]
    if not records:
        return None
    first = run.window_steps()
    if first and first[0].end < kept[0].start:
        raise LookupError(
            f"the step log keeps {len(kept)} records, the oldest from "
            f"{kept[0].start:.6f} s; the window's first step ended at "
            f"{first[0].end:.6f} s, so its record was dropped")
    engines = {t.engine for t in records}
    if len(engines) > 1:
        raise ValueError(f"the window holds steps of several engines: "
                         f"{sorted(engines)}")
    return records
