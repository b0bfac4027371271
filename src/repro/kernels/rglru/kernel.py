"""RG-LRU linear-recurrence Pallas TPU kernel (RecurrentGemma).

The recurrence h_t = a_t * h_{t-1} + b_t is sequential in time but fully
parallel across the width lanes — the natural TPU mapping is: width on the
128-lane vector axis, time as a fori_loop inside a block, and the running
state h in VMEM scratch carried across the innermost (sequential) sequence
grid dimension. No matmuls: this is a VPU kernel.

Layout contract: a, b (B, S, W); grid = (B, n_w, n_s), n_s sequential.
Outputs: y (B, S, W) and final state (B, W).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend


def _rglru_kernel(a_ref, b_ref, y_ref, h_out_ref, h_scr, *, s_block: int):
    si = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def step(t, h):
        a_t = a_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)
        h = a_t * h + b_t
        y_ref[0, t, :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, s_block, step, h_scr[0])
    h_scr[0] = h

    @pl.when(si == n_s - 1)
    def _emit():
        h_out_ref[0] = h_scr[0].astype(h_out_ref.dtype)


def rglru_scan(a, b, *, s_block: int = 128, w_block: int = 128,
               interpret: bool | None = None):
    """a, b: (B, S, W). Returns (y (B,S,W) fp32-accurate, h_final (B,W))."""
    bsz, s, w = a.shape
    s_block = min(s_block, s)
    w_block = min(w_block, w)
    assert s % s_block == 0 and w % w_block == 0
    n_s, n_w = s // s_block, w // w_block

    kernel = functools.partial(_rglru_kernel, s_block=s_block)
    return pl.pallas_call(
        kernel,
        grid=(bsz, n_w, n_s),
        in_specs=[
            pl.BlockSpec((1, s_block, w_block), lambda i, wi, si: (i, si, wi)),
            pl.BlockSpec((1, s_block, w_block), lambda i, wi, si: (i, si, wi)),
        ],
        out_specs=[
            pl.BlockSpec((1, s_block, w_block), lambda i, wi, si: (i, si, wi)),
            pl.BlockSpec((1, w_block), lambda i, wi, si: (i, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, w), a.dtype),
            jax.ShapeDtypeStruct((bsz, w), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, w_block), jnp.float32)],
        interpret=backend.interpret(interpret),
    )(a, b)
