"""Grouped (per-expert) matmul Pallas TPU kernel for MoE FFNs.

After capacity-grouped dispatch the MoE FFN is a batch of E independent
(C × D) @ (D × F) matmuls. The kernel tiles each expert's matmul for the MXU
with a VMEM fp32 accumulator across the innermost (sequential) K dimension —
the standard TPU matmul pattern with an expert grid axis in front, which is
what makes expert-parallel sharding compose: the expert axis is embarrassingly
parallel and shards over the `model` mesh axis via XAIF's port contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend


def _gmm_kernel(x_ref, w_ref, o_ref, acc_scr):
    di = pl.program_id(3)
    n_d = pl.num_programs(3)

    @pl.when(di == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(di == n_d - 1)
    def _emit():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def grouped_matmul(x, w, *, c_block: int = 128, f_block: int = 128,
                   d_block: int = 256, interpret: bool | None = None):
    """x: (E, C, D), w: (E, D, F) -> (E, C, F)."""
    e, c, d = x.shape
    f = w.shape[-1]
    c_block = min(c_block, c)
    f_block = min(f_block, f)
    d_block = min(d_block, d)
    assert c % c_block == 0 and f % f_block == 0 and d % d_block == 0
    grid = (e, c // c_block, f // f_block, d // d_block)

    return pl.pallas_call(
        _gmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c_block, d_block), lambda ei, ci, fi, di: (ei, ci, di)),
            pl.BlockSpec((1, d_block, f_block), lambda ei, ci, fi, di: (ei, di, fi)),
        ],
        out_specs=pl.BlockSpec((1, c_block, f_block),
                               lambda ei, ci, fi, di: (ei, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((c_block, f_block), jnp.float32)],
        interpret=backend.interpret(interpret),
    )(x, w)
