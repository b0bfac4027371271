"""The hybrid's cell through the whole harness at a CPU size: the engine
serves granite-4.0-h-micro's equations cut to its model file's ``small``,
the check compares the served tokens with the float32 reference, and the
float8 control, read at the same positions, fails where the program passes.

At this size, over three seeds, the program read a widest gap of
7e-6–6e-5 and the float8 control 8e-4–3e-3 (the tied head over an
embedding drawn at 0.02/12 gives logits a tenth of the dense cells'); the
limit here, 4e-4, lies between them. The chip's own readings and limit are
in ``bench/limits/granite-4.0-h-micro.decode.json``.
"""

import dataclasses
import time

import pytest

from bench import harness, spec
from smallcell import PEAKS

LIMIT = 4e-4


def _cell():
    base = spec.cell("granite-4.0-h-micro.decode")
    cfg = dict(spec.model(base.config).small(base.config), name="hybrid-small")
    mix = dict(base.traffic, check_tokens=120)
    mix["prompt"] = dict(mix["prompt"], median=20, sigma=0.5, min=4, max=40)
    mix["output"] = dict(mix["output"], median=24, sigma=0.6, min=4, max=48)
    return dataclasses.replace(base, config=cfg, traffic=mix,
                               limits={"max_logit_gap": LIMIT})


@pytest.mark.parametrize("seed", [2**31 + 11, 2**31 + 13])
def test_hybrid_cell_passes_and_its_control_fails(seed):
    _, out = harness.run_cell(_cell(), seed, 3.0, False, time.perf_counter(),
                              PEAKS, control=True)
    sound = out["checks"]["max_logit_gap"]["value"]
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_compared"]["value"] >= 100
    assert out["control_gap"] > LIMIT
    assert out["control_gap"] >= 3 * sound
