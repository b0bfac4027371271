"""Pure-jax oracle of the Mamba-2 decode recurrence over a state arena.

One token of the selective state-space update for a batch of lanes, in
float32, with every product written elementwise:

    h ← h·exp(dt·A) + (dt·x) ⊗ B        y = Σ_n h·C + D·x

per head (``H`` heads of ``P`` channels, a state of ``N`` a channel; B and
C are shared by the heads of the one group). :func:`ssm_step` is the
recurrence on a per-lane state; :func:`ssm_decode` applies it to one
layer's rows of a state *arena* ``(Lm, B, H, P, N)`` — the serving
engine's per-slot state, one row of a layer per slot — and leaves masked
lanes' rows bitwise as they were. The Pallas kernel (``kernel.py``) has
the same contract, reading and writing the arena in place.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def ssm_step(state, x, dt, A, B, C, D):
    """One recurrence step. state (b, H, P, N) f32; x (b, H, P) the
    mixer's activations after the conv; dt (b, H) f32 after softplus;
    A (H,) f32, negative; B, C (b, N); D (H,) f32. Returns
    ``(y (b, H, P) f32, state' (b, H, P, N) f32)``."""
    xf = x.astype(F32)
    decay = jnp.exp(dt * A)[..., None, None]
    inject = (dt[..., None] * xf)[..., None] * B.astype(F32)[:, None, None, :]
    state = state * decay + inject
    y = jnp.sum(state * C.astype(F32)[:, None, None, :], axis=-1)
    return y + D[:, None] * xf, state


def ssm_decode(state, layer, x, dt, A, B, C, D, mask=None):
    """:func:`ssm_step` on layer ``layer`` of the arena ``state``
    (Lm, b, H, P, N); lanes where ``mask`` (b,) is False keep their rows
    bitwise. Returns ``(y, state')``."""
    old = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    y, new = ssm_step(old, x, dt, A, B, C, D)
    if mask is not None:
        new = jnp.where(mask[:, None, None, None], new, old)
    return y, lax.dynamic_update_index_in_dim(state, new, layer, 0)
