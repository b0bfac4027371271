"""Readings that a cell's limit is set from: the program's widest gap and
the float8 control's, seed by seed, in one process on the chip.

  python3 -m bench.control granite-3-2b.chat 45 101 102 103

Each seed is one whole run of the cell at its own size and load. After
the window, the sample of served tokens is compared with the float32
reference (the program's reading, as every run makes it) and read again
with the reference computed in float8 in the program's place (the
control). The limit in ``bench/limits/<cell>.json`` must lie above the
largest program reading and below the smallest control reading. The
benchmark's own runs do not run this.
"""

from __future__ import annotations

import json
import sys
import time


def main(argv) -> int:
    from bench import harness, spec
    from bench.run import open_chip

    name, seconds, seeds = argv[0], float(argv[1]), [int(s) for s in argv[2:]]
    if open_chip(name) is None:
        return 2
    cell = spec.cell(name)
    peaks = harness.device_peaks()
    rows = []
    for seed in seeds:
        _, out = harness.run_cell(cell, seed, seconds, False,
                                  time.perf_counter(), peaks, control=True)
        row = {"seed": seed,
               "program": out["checks"]["max_logit_gap"]["value"],
               "control": out["control_gap"],
               "tokens": out["checks"]["tokens_compared"]["value"],
               "requests": out["requests_compared"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"cell": name, "seeds": len(rows),
                      "program_max": max(r["program"] for r in rows),
                      "control_min": min(r["control"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
