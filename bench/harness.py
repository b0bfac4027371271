"""One run of one cell: set-up, the measured window, the check, the metrics.

``run_cell`` takes a cell (``bench.spec.Cell``) and drives the program's
engine with the cell's traffic for ``seconds``. It records, on the host
clock, when each request was due, admitted and given each token, and,
for each ``engine.step()``, its start and end, the tokens it fed and the
key positions they attended over. Readers under ``bench/metrics`` turn
that record (``Run``) into metrics. Correctness is decided afterwards by
``bench.check`` against the float32 reference.
"""

from __future__ import annotations

import dataclasses
import gc
import pathlib
import shutil
import tempfile
import time

import jax

from bench import check, spec, traffic, weights
from bench import trace as trace_mod
from bench.spec import Cell

WARM_PROMPT, WARM_OUTPUT = 3, 2
DRAIN_S = 150.0      # longest wait for first tokens after the window
BACKLOG = 4096       # requests a backlog can draw on: more than a window ends


@dataclasses.dataclass
class ReqRecord:
    id: str
    prompt_len: int
    output_len: int
    due: float                      # host clock
    in_window: bool
    submit: float | None = None
    stamps: list = dataclasses.field(default_factory=list)
    request: object = None          # the engine's Request


@dataclasses.dataclass
class StepRecord:
    start: float
    end: float
    fed: int           # tokens fed (one query each)
    attended: int      # key positions those queries attended over
    emitted: int       # tokens handed out to requests


@dataclasses.dataclass
class Run:
    cell: Cell
    seconds: float
    t0: float                       # window opens
    t1: float                       # window closes (end of last step in it)
    setup_s: float
    reqs: list[ReqRecord]
    steps: list[StepRecord]
    peaks: dict
    trace: trace_mod.TraceSummary | None = None
    traced: tuple[float, float] | None = None   # host clock of the trace

    @property
    def cfg(self) -> dict:
        return self.cell.config

    def window_steps(self, span: tuple[float, float] | None = None):
        a, b = span or (self.t0, self.t1)
        return [s for s in self.steps if s.start >= a and s.end <= b]


class _Stamper:
    """Stamps every token a step hands out with the time the step ended,
    and counts the tokens each step fed and attended over."""

    def __init__(self, engine, records: dict[str, ReqRecord]):
        self.engine = engine
        self.records = records
        self.n_done = 0

    def _live(self):
        return [s for s in self.engine.slots if s is not None]

    def step(self) -> StepRecord:
        eng = self.engine
        before = {id(s.request): s.fed for s in self._live()}
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            eng.step()
        end = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.stamp"):
            fed = attended = emitted = 0
            after = self._live()
            for s in after:
                k = id(s.request)
                fed += s.fed - before.pop(k, 0)
                attended += s.fed
            done = eng.completed[self.n_done:]
            self.n_done = len(eng.completed)
            for r in done:   # finished in this step: fed their last token
                if id(r) in before:
                    fed += 1
                    attended += before.pop(id(r)) + 1
            for r in [s.request for s in after] + done:
                rec = self.records.get(r.id)
                if rec is None:
                    continue
                new = len(r.tokens) - len(rec.stamps)
                rec.stamps += [end] * new
                emitted += new
        return StepRecord(start, end, fed, attended, emitted)


def warm_up(engine, cfg: dict, sut, rng) -> None:
    """Compile every program the window drives: fill every slot with a
    tiny request (prefill, decode, finish, admission) and run until idle.
    The prompts are fresh ids shorter than a page, so nothing they leave
    in the prefix cache can match a measured request."""
    vocab = cfg["vocab_size"]
    for i in range(cfg["engine"]["slots"]):
        engine.submit(sut.Request(
            f"warm{i}", rng.integers(0, vocab, WARM_PROMPT).tolist(),
            WARM_OUTPUT))
    while engine.step():
        pass


def _open_loop(engine, sut, stamper, plan, t0, seconds, records):
    """Submit each request when due; step while there is work. After the
    window closes, step on until every request due in it has its first
    token (or ``DRAIN_S`` has passed)."""
    i, n = 0, len(plan)
    steps = []
    late = 0.0
    t_close = t0 + seconds
    while True:
        now = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.submit"):
            while i < n and t0 + plan[i][0].due <= now:
                a, prompt = plan[i]
                rec = records[f"r{i}"]
                rec.submit = now
                late = max(late, now - rec.due)
                req = sut.Request(rec.id, prompt(), a.output_len)
                req.arrival_time = rec.due
                rec.request = req
                if not engine.submit(req):
                    raise RuntimeError(f"engine refused {rec.id}")
                i += 1
        if now >= t_close and (now >= t_close + DRAIN_S or all(
                r.stamps for r in records.values() if r.request is not None)):
            break
        if engine.busy:
            steps.append(stamper.step())
        elif now >= t_close:
            break                      # idle with a request unserved: failed
        else:
            nxt = t0 + plan[i][0].due if i < n else t_close
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, nxt - time.perf_counter()))
    return steps, late


def _backlog(engine, sut, stamper, plan, t0, seconds, records, depth):
    """Keep at least ``depth`` requests queued; step until the window ends."""
    i = 0
    steps = []

    def top_up():
        nonlocal i
        while len(engine.queue) < depth:
            a, prompt = plan[i]
            rec = records[f"r{i}"]
            rec.due = rec.submit = time.perf_counter()
            req = sut.Request(rec.id, prompt(), a.output_len)
            rec.request = req
            engine.submit(req)
            i += 1

    while True:
        with jax.profiler.TraceAnnotation("bench.submit"):
            top_up()
        if time.perf_counter() >= t0 + seconds:
            break
        steps.append(stamper.step())
    return steps, 0.0


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             peaks: dict, log=lambda msg: None, engine_hook=None,
             control: bool = False) -> tuple[Run, dict]:
    """Set up, measure for ``seconds``, check. Returns the record and the
    check's outcome. ``engine_hook(engine)``, when given, is called on the
    built engine before warm-up (tests plant faults through it);
    ``control`` also reads the low-precision control (``bench/control.py``)."""
    from bench import sut

    cfg, mix = cell.config, cell.traffic
    params = jax.block_until_ready(
        weights.make(cfg, spec.seed32(seed, "weights")))
    engine = sut.build_engine(cfg, params)
    if engine_hook is not None:
        engine_hook(engine)
    warm_up(engine, cfg, sut, spec.rng(seed, "warm-up"))

    rng = spec.rng(seed, "traffic")
    if mix["loop"] == "open":
        arrivals = traffic.open_loop(mix, seconds, rng)
    elif mix["loop"] == "backlog":
        # more than a window can consume at any plausible step time
        arrivals = traffic.backlog(mix, BACKLOG, rng)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    tok_rng = spec.rng(seed, "tokens")
    vocab = cfg["vocab_size"]
    # prompts are drawn as requests are submitted, in plan order
    plan = [(a, lambda a=a: tok_rng.integers(0, vocab, a.prompt_len).tolist())
            for a in arrivals]
    gc.collect()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    records = {f"r{i}": ReqRecord(f"r{i}", a.prompt_len, a.output_len,
                                  t0 + a.due, mix["loop"] == "open")
               for i, (a, _) in enumerate(plan)}
    stamper = _Stamper(engine, records)
    stamper.n_done = len(engine.completed)

    tracer = _Tracer(trace, seconds, t0)
    engine_step = stamper.step

    def traced_step():
        tracer.maybe_start()
        rec = engine_step()
        tracer.maybe_stop(rec.end)
        return rec

    stamper.step = traced_step
    if mix["loop"] == "open":
        steps, late = _open_loop(engine, sut, stamper, plan, t0, seconds,
                                 records)
        t1 = t0 + seconds
    else:
        steps, late = _backlog(engine, sut, stamper, plan, t0, seconds,
                               records, depth=cfg["engine"]["slots"])
        t1 = steps[-1].end if steps else time.perf_counter()
        for rec in records.values():   # attempted: admitted in the window
            rec.in_window = (rec.request is not None
                             and rec.request.admit_time is not None)
    tracer.stop()
    device = jax.devices()[0]
    mem = (device.memory_stats() or {}).get("peak_bytes_in_use")
    stats = engine.stats()
    finished = [r for r in records.values() if r.request is not None
                and r.request.finish_time is not None]
    log(f"window {seconds} s: {len(steps)} steps, "
        f"{sum(r.in_window for r in records.values())} requests due, "
        f"{len(finished)} finished, {sum(s.emitted for s in steps)} tokens "
        f"stamped; generator at most {late * 1e3:.1f} ms late; engine "
        f"rejected {stats['rejected']}, token faults {stats['token_faults']}, "
        f"replays {stats['replays']}, prompt tokens reused "
        f"{stats['prompt_tokens_reused']}; peak HBM {mem} bytes")
    run = Run(cell, seconds, t0, t1, setup_s, list(records.values()), steps,
              peaks, traced=tracer.span)
    if trace:
        run.trace = trace_mod.load(tracer.dir)
        shutil.rmtree(tracer.dir, ignore_errors=True)
    del engine, stamper, traced_step, engine_step
    gc.collect()
    outcome = check.check(cell, params, [r.request for r in finished], seed,
                          log, control=control)
    outcome["memory_peak_bytes"] = mem
    # open loop: a request due in the window that never got a token failed
    outcome["failed"] = stats["rejected"] + stats["shed"] + (
        sum(1 for r in records.values() if r.in_window and not r.stamps)
        if mix["loop"] == "open" else 0)
    outcome["attempted"] = sum(r.in_window for r in records.values())
    return run, outcome


class _Tracer:
    """The profiler around a stretch of the window: the middle third,
    at most ``MAX_S`` seconds, so the trace stays small."""

    MAX_S = 10.0

    def __init__(self, on: bool, seconds: float, t0: float):
        self.on = on
        length = min(self.MAX_S, seconds / 3)
        self.a = t0 + (seconds - length) / 2
        self.b = self.a + length
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if on else None
        self.started = self.stopped = False
        self.span = None
        self._ann = None

    def maybe_start(self):
        if self.on and not self.started and time.perf_counter() >= self.a:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
            self._ann.__enter__()
            self.started = True
            self.span = [time.perf_counter(), None]

    def maybe_stop(self, now):
        if self.started and not self.stopped and now >= self.b:
            self.stop()

    def stop(self):
        if self.started and not self.stopped:
            self.span[1] = time.perf_counter()
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.stopped = True
            self.span = tuple(self.span)


def device_peaks() -> dict:
    """The peak table's entry for this device kind (unknown kinds fail)."""
    kind = jax.devices()[0].device_kind
    table = spec.load_json(pathlib.Path(__file__).parent / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]
