"""Mamba-2 decode recurrence, Pallas TPU kernel, on a state arena in place.

The serving engine keeps every Mamba-2 layer's per-slot state in one
float32 arena ``(Lm, B, H, P, N)``. Each decode step reads each lane's
state of a layer once and writes it once: the arena is aliased to the
kernel's second output, the layer index rides in as a scalar-prefetch
operand that picks the arena rows in the block index map, and the grid
``(B, H / heads_per_block)`` visits that layer's rows only — so nothing
of the arena is sliced out, copied or stacked, and every other layer's
rows are left where they lie. A lane whose ``live`` flag is 0 writes back
the block it read, so its state stays bitwise unchanged.

In each grid step, for ``heads_per_block`` heads of one lane::

    h ← h·exp(dt·A) + (dt·x) ⊗ B        y = Σ_n h·C + D·x

all in float32 on the vector unit (the products elementwise, as in
``ref.py``); x, B and C arrive in the activations' dtype. Per-head
scalars (dt, A, D) ride as ``(…, heads, 1)`` columns so they broadcast
over a head's ``(P, N)`` state tile.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

F32 = jnp.float32
BLOCK_BYTES = 1 << 20       # one block of state: at most 1 MiB


def _heads_per_block(h: int, p: int, n: int) -> int:
    """All heads when a lane's state fits one block, else the largest
    multiple of 8 dividing ``h`` whose block does."""
    if h * p * n * 4 <= BLOCK_BYTES:
        return h
    fits = [k for k in range(8, h, 8) if h % k == 0 and k * p * n * 4 <= BLOCK_BYTES]
    return max(fits) if fits else h


def _kernel(layer_ref, live_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref,
            s_ref, y_ref, s_out):
    del layer_ref                        # used by the index maps only
    h = s_ref[0, 0]                      # (hb, P, N) f32
    x = x_ref[0].astype(F32)             # (hb, P)
    dt = dt_ref[0]                       # (hb, 1)
    decay = jnp.exp(dt * a_ref[...])     # (hb, 1)
    inject = (dt * x)[:, :, None] * b_ref[0].astype(F32)[None]
    new = h * decay[:, :, None] + inject
    y = jnp.sum(new * c_ref[0].astype(F32)[None], axis=-1)
    y_ref[0] = y + d_ref[...] * x
    s_out[0, 0] = jnp.where(live_ref[pl.program_id(0)] != 0, new, h)


def ssm_decode_kernel(state, layer, x, dt, A, B, C, D, mask=None, *,
                      interpret: bool | None = None):
    """Same contract as ``ref.ssm_decode``: state (Lm, b, H, P, N) f32,
    updated at layer ``layer`` (a scalar int) for lanes where ``mask``;
    x (b, H, P); dt (b, H) f32; A, D (H,) f32; B, C (b, N). Returns
    ``(y (b, H, P) f32, state')`` — donate ``state`` to update it in
    place."""
    _, b, h, p, n = state.shape
    hb = _heads_per_block(h, p, n)
    live = (jnp.ones((b,), jnp.int32) if mask is None
            else mask.astype(jnp.int32))
    lane = lambda i, j, l, m: (i, j, 0)        # noqa: E731
    head = lambda i, j, l, m: (j, 0)           # noqa: E731
    row = lambda i, j, l, m: (i, 0, 0)         # noqa: E731
    arena = lambda i, j, l, m: (l[0], i, j, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // hb),
        in_specs=[
            pl.BlockSpec((1, hb, p), lane),
            pl.BlockSpec((1, hb, 1), lane),
            pl.BlockSpec((hb, 1), head),
            pl.BlockSpec((1, 1, n), row),
            pl.BlockSpec((1, 1, n), row),
            pl.BlockSpec((hb, 1), head),
            pl.BlockSpec((1, 1, hb, p, n), arena),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, p), lane),
            pl.BlockSpec((1, 1, hb, p, n), arena),
        ],
    )
    y, state = pl.pallas_call(
        _kernel,
        name="ssm_decode",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, p), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 8 (after the two scalar-prefetch ones) is the arena
        input_output_aliases={8: 1},
        interpret=backend.interpret(interpret),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), live, x,
      dt.astype(F32)[..., None], A.astype(F32)[:, None], B[:, None],
      C[:, None], D.astype(F32)[:, None], state)
    return y, state
