"""Depthwise causal conv1d Pallas TPU kernel — the framework's "CGRA".

This is the accelerator of the paper's healthcare integration example
(HEEPocrates runs its seizure-CNN convolutions on a 4-PE CGRA for a 4.9×
energy win). The TPU adaptation: channels ride the 128-lane vector axis
(≙ the CGRA's parallel PEs), taps are unrolled (≙ the CGRA context-memory
program), and the causal halo is stitched from the PREVIOUS sequence block
via a second BlockSpec view — no gather, no HBM round-trip for the overlap.

Layout: x (B, S, D), w (W, D), depthwise: y[t,d] = Σ_i w[i,d]·x[t-W+1+i,d].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend


def _conv_kernel(x_ref, xprev_ref, w_ref, o_ref, *, width: int, s_block: int):
    si = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)          # (bs, bd)
    prev = xprev_ref[0].astype(jnp.float32)   # (bs, bd) — previous block
    halo = prev[-(width - 1):]                # (W-1, bd)
    halo = jnp.where(si == 0, jnp.zeros_like(halo), halo)  # causal start
    xcat = jnp.concatenate([halo, x], axis=0)  # (bs+W-1, bd)
    w = w_ref[...].astype(jnp.float32)        # (W, bd)
    acc = jnp.zeros((s_block, x.shape[1]), jnp.float32)
    for i in range(width):                    # taps unrolled (CGRA program)
        acc += xcat[i:i + s_block] * w[i]
    o_ref[0] = acc.astype(o_ref.dtype)


def conv1d_causal(x, w, *, s_block: int = 256, d_block: int = 128,
                  interpret: bool | None = None):
    """x: (B, S, D), w: (W, D) -> (B, S, D)."""
    b, s, d = x.shape
    width = w.shape[0]
    s_block = min(s_block, s)
    d_block = min(d_block, d)
    assert s % s_block == 0 and d % d_block == 0
    assert s_block >= width - 1, "block must cover the halo"
    grid = (b, s // s_block, d // d_block)

    kernel = functools.partial(_conv_kernel, width=width, s_block=s_block)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s_block, d_block), lambda i, si, di: (i, si, di)),
            # previous block view for the halo (clamped at the left edge)
            pl.BlockSpec((1, s_block, d_block),
                         lambda i, si, di: (i, jnp.maximum(si - 1, 0), di)),
            pl.BlockSpec((width, d_block), lambda i, si, di: (0, di)),
        ],
        out_specs=pl.BlockSpec((1, s_block, d_block),
                               lambda i, si, di: (i, si, di)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), x.dtype),
        interpret=backend.interpret(interpret),
    )(x, x, w)
