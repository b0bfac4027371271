"""Find the knee of an open-loop cell: the highest offered rate the engine
sustains without a growing queue. Run once on the chip when a cell is
defined; the cell's traffic file then offers a fixed share of it.

  python3 -m bench.knee granite-3-2b.chat 30 2.0 2.5 3.0 3.5 4.0

For each rate (requests/s), one run of the cell at that rate for the
given seconds, all in this process. A rate is sustained when requests
due in the last third of the window waited for admission no longer, at
the median, than those of the first third plus one second.
"""

from __future__ import annotations

import dataclasses
import json
import statistics
import sys
import time


def queue_waits(run, lo: float, hi: float) -> list[float]:
    a, b = run.t0 + lo * run.seconds, run.t0 + hi * run.seconds
    return [r.request.admit_time - r.due for r in run.reqs
            if a <= r.due < b and r.request is not None
            and r.request.admit_time is not None]


def main(argv) -> int:
    from bench import harness, spec
    from bench.run import open_chip
    from bench.stats import percentile, ttft_ms

    name, seconds, rates = argv[0], float(argv[1]), [float(r) for r in argv[2:]]
    if open_chip(name) is None:
        return 2
    base = spec.cell(name)
    peaks = harness.device_peaks()
    rows = []
    for rate in rates:
        cell = dataclasses.replace(
            base, traffic=dict(base.traffic, rate_rps=rate))
        run, _ = harness.run_cell(cell, 1000 + int(rate * 100), seconds,
                                  False, time.perf_counter(), peaks)
        first = queue_waits(run, 0, 1 / 3)
        last = queue_waits(run, 2 / 3, 1)
        ttft = ttft_ms(run)
        row = {"rate_rps": rate, "requests": len(ttft),
               "ttft_p50_ms": percentile(ttft, 50),
               "ttft_p90_ms": percentile(ttft, 90),
               "wait_first_third_s": statistics.median(first) if first else None,
               "wait_last_third_s": statistics.median(last) if last else None,
               "step_ms": statistics.median(
                   (s.end - s.start) * 1e3 for s in run.window_steps())}
        row["sustained"] = bool(first and last and row["wait_last_third_s"]
                                <= row["wait_first_third_s"] + 1.0)
        rows.append(row)
        print(json.dumps(row), flush=True)
    ok = [r["rate_rps"] for r in rows if r["sustained"]]
    print(json.dumps({"cell": name, "knee_rps": max(ok) if ok else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
