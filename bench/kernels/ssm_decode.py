"""Operations and bytes that the Mamba-2 decode kernel needs for one step.

Counted from what the step asks of it: each token fed (one live lane)
reads and writes its float32 state once in every layer that holds
recurrent state (the model file's ``ssm_layers``), and moves x, B, C and
dt in and y out at their dtypes (bf16 activations, float32 dt and y).
Its operations are the recurrence's multiply-adds, ``5 · H · P · N`` a
lane and layer: the decay, the injection and its add, and the readout's
product and sum. The conv and the projections are XLA ops outside the
kernel and are not counted.
"""

from __future__ import annotations

from bench import spec

# the name the program gives the kernel's ``pallas_call``, which the TPU
# trace keeps as the custom call's instruction name
TRACE_NAMES = ("ssm_decode",)


def cost(cfg: dict, lanes: int) -> tuple[float, float]:
    """(FLOPs, bytes) over ``lanes`` live lanes, in every layer that holds
    recurrent state."""
    flops = nbytes = 0.0
    for h, p, n in spec.model(cfg).ssm_layers(cfg):
        flops += 5.0 * h * p * n
        nbytes += 2 * h * p * n * 4           # the state, read and written
        nbytes += h * p * 2 + 2 * n * 2       # x, B and C in (bf16)
        nbytes += h * 4 + h * p * 4           # dt in and y out (f32)
    return flops * lanes, nbytes * lanes
