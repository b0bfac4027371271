"""Host spans and per-step counters of the serving engine.

Every :meth:`ContinuousBatchingEngine.step` that does work leaves two
traces of itself:

* **Spans** in the profiler's host plane. ``engine.step`` covers the
  call; inside it one span per phase, none nested in another but
  ``engine.state_reset``, which runs inside ``engine.admit``:

  ================== =======================================================
  span               what the engine does in it
  ================== =======================================================
  engine.meter       the energy meter's clock tick and its per-step charge
  engine.admit       admission: page-table acquire, journal open, lane seed
  engine.state_reset (models with recurrent state) the launch that zeroes
                     an admitted slot's rows of the state arena
  engine.batch       the batch: tokens, counts, emit flags, pages to write
  engine.tables      block tables and lengths, and their host→device copies
  engine.launch      argument conversion and the jitted call, until it
                     returns (the step is enqueued, not finished)
  engine.account     bookkeeping after the launch: fed, publish, evict
  engine.wait        the wait for the step's tokens (``jax.device_get``)
  engine.retire      token delivery, journal and completions
  engine.recover     quarantine recovery (requeue of faulted requests)
  ================== =======================================================

  The spans are ``jax.profiler.TraceAnnotation``\\ s, so a profiler
  trace puts each device op and each idle gap beside the host work that
  ran at the same instant.

* **One** :class:`StepTrace` in a bounded, process-wide log
  (:data:`LOG_BOUND` records; the oldest are dropped first), read with
  :func:`steps`. Its times come from ``time.perf_counter`` — never from
  the engine's ``clock``, which a simulation may fake — and its phase
  seconds are the spans' durations by name.

The log is always on. With no profiler running, a span costs an
annotation that records nothing and two clock reads.
"""

from __future__ import annotations

import collections
import dataclasses
import time

from jax.profiler import TraceAnnotation

__all__ = ["LOG_BOUND", "StepTrace", "span", "record", "steps"]

LOG_BOUND = 65536

_LOG: collections.deque = collections.deque(maxlen=LOG_BOUND)


@dataclasses.dataclass(slots=True)
class StepTrace:
    """What one engine step did, on the ``perf_counter`` clock.

    ``launched`` is when the jitted call returned (the step is enqueued),
    ``waited`` when the wait for a step's tokens ended; either is None in
    a step that did not launch or did not wait. ``phases`` maps a span
    name to its seconds in this step. ``prompt_fed`` and ``decode_fed``
    are the prompt and decode tokens fed to the device, ``emitted`` the
    tokens the step generates, ``admitted`` the requests it admitted,
    ``queued`` the queue depth after admission and ``state_resets`` the
    slots whose recurrent state it zeroed."""

    engine: str
    start: float
    end: float = 0.0
    launched: float | None = None
    waited: float | None = None
    phases: dict = dataclasses.field(default_factory=dict)
    prompt_fed: int = 0
    decode_fed: int = 0
    emitted: int = 0
    admitted: int = 0
    queued: int = 0
    state_resets: int = 0


class span:
    """``with span(name, trace):`` — a profiler annotation ``name`` whose
    ``perf_counter`` duration is added to ``trace.phases[name]`` (summed
    when the phase recurs in a step). ``trace`` None annotates only: the
    engine does step work outside ``step()`` too (a preemption's flush)."""

    __slots__ = ("name", "trace", "_ann", "_t")

    def __init__(self, name: str, trace: StepTrace | None):
        self.name = name
        self.trace = trace

    def __enter__(self):
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t
        self._ann.__exit__(*exc)
        if self.trace is not None:
            phases = self.trace.phases
            phases[self.name] = phases.get(self.name, 0.0) + dt
        return False


def record(trace: StepTrace) -> None:
    """Append ``trace`` to the log, dropping the oldest record when full."""
    _LOG.append(trace)


def steps(engine: str | None = None, since: float | None = None
          ) -> list[StepTrace]:
    """The logged steps of the engine named ``engine`` (every engine's when
    None) that started at or after ``since`` (``perf_counter`` seconds),
    oldest first."""
    return [t for t in _LOG
            if (engine is None or t.engine == engine)
            and (since is None or t.start >= since)]
