"""Reduce a profiler trace (``.xplane.pb``) to device busy time, op time
and idle gaps, read with ``jax.profiler.ProfileData``.

* Busy is the union of the intervals in which an op of the ``XLA Ops``
  line of a TPU plane runs, clipped to the traced window; the window is
  the harness's own ``bench.window`` span on the host.
* Op time is the sum of each op's device durations in the window, by a
  short label of the HLO instruction the TPU trace names each op with
  (``%name opcode``, plus the target of a custom call). Loops and
  conditionals hold the ops they run, so they count toward busy time and
  not toward op time.
* An idle gap is a stretch of the window with no op running. Each is
  charged to the innermost ``bench.*`` host span open at its midpoint.
"""

from __future__ import annotations

import collections
import dataclasses
import pathlib
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    n_devices: int
    op_seconds: dict[str, float]          # op name -> device seconds
    idle_by_span: dict[str, float]        # host span -> idle seconds

    def seconds_of(self, names: tuple[str, ...]) -> float:
        """Device seconds of every op whose name holds one of ``names``."""
        return sum(s for op, s in self.op_seconds.items()
                   if any(n in op for n in names))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(log_dir) -> pathlib.Path:
    paths = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


_HLO = re.compile(r"^(%[\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
CONTAINERS = ("while", "conditional", "call")


def _op_label(name: str) -> tuple[str, str]:
    """(label, opcode) of an op named by its HLO instruction text."""
    m = _HLO.match(name)
    if not m:
        return name[:120], ""
    label = f"{m.group(1)} {m.group(2)}"
    target = _TARGET.search(name)
    return (f"{label} {target.group(1)}" if target else label), m.group(2)


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(data) -> TraceSummary:
    """Summarise a ``jax.profiler.ProfileData``."""
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    w0, w1 = windows[0]
    devices = [p for p in data.planes if p.name.startswith("/device:TPU:")]
    op_ns: dict[str, float] = collections.defaultdict(float)
    busy_ns = 0.0
    gaps = []
    for plane in devices:
        ivs = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e > s:
                    ivs.append((s, e))
                    label, opcode = _op_label(ev.name)
                    if opcode not in CONTAINERS:
                        op_ns[label] += e - s
        merged = _union(ivs)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = max(len(devices), 1)
    idle: dict[str, float] = collections.defaultdict(float)
    inner = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    for s, e in gaps:
        mid = (s + e) / 2
        open_ = [sp for sp in inner if sp[0] <= mid < sp[1]]
        name = max(open_)[2] if open_ else "(no span)"
        idle[name] += (e - s) / 1e9 / n
    return TraceSummary(
        window_s=(w1 - w0) / 1e9, busy_s=busy_ns / 1e9 / n,
        n_devices=len(devices),
        op_seconds={k: v / 1e9 / n for k, v in op_ns.items()},
        idle_by_span=dict(idle))


def load(log_dir) -> TraceSummary:
    import jax

    return reduce(jax.profiler.ProfileData.from_file(str(find_xplane(log_dir))))
