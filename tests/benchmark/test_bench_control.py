"""The control: the reference in float8 in the program's place must fail
the comparison that the program passes.

At this CPU size, over seeds 0-3 of both mixes, the program read a widest
gap of 0-0.0088 and the float8 control 0.060-0.186; the limit here, 0.03,
lies between them. The chip's own readings and limits are in
``bench/limits``.
"""

import pytest

from smallcell import run, small_cell

LIMIT = 0.03


@pytest.mark.parametrize("loop", ["open", "backlog"])
def test_control_fails_where_the_program_passes(loop):
    _, out = run(small_cell(f"control-{loop}", loop, LIMIT), control=True)
    sound = out["checks"]["max_logit_gap"]["value"]
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_compared"]["value"] >= 100
    assert out["control_gap"] > LIMIT
    assert out["control_gap"] >= 3 * sound
