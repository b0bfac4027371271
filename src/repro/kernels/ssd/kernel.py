"""Mamba-2 SSD chunk-scan Pallas TPU kernel.

TPU adaptation of the SSD insight (state-space duality): the sequence is
processed in chunks; within a chunk everything is dense matmul work for the
MXU (intra-chunk scores through a decay mask), and the O(state) recurrence
only crosses chunk boundaries — carried here in VMEM scratch across the
innermost sequential grid dimension, so the state never round-trips to HBM.

Layout contract (head-major): x (BH, S, P), dA (BH, S, 1), B/C (BH, S, N);
outputs y (BH, S, P) and final state (BH, P, N). Grid = (BH, n_chunks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend


def _ssd_kernel(x_ref, da_ref, b_ref, c_ref, y_ref, st_out_ref, state_scr, *,
                chunk: int):
    ci = pl.program_id(1)
    n_c = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)      # (Q, P)
    da = da_ref[0, :, 0].astype(jnp.float32)  # (Q,)
    b = b_ref[0].astype(jnp.float32)      # (Q, N)
    c = c_ref[0].astype(jnp.float32)      # (Q, N)

    a_cs = jnp.cumsum(da)                 # (Q,)
    # intra-chunk: scores[i,j] = (C_i · B_j) * exp(A_cs[i]-A_cs[j]) for j<=i
    seg = a_cs[:, None] - a_cs[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(jj <= ii, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * decay
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the incoming state
    state = state_scr[...]                # (P, N)
    y += jax.lax.dot_general(c, state, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        * jnp.exp(a_cs)[:, None]

    # state update: state' = state*exp(A_total) + X^T (B * decay_to_end)
    decay_states = jnp.exp(a_cs[-1] - a_cs)[:, None] * b   # (Q, N)
    state_scr[...] = state * jnp.exp(a_cs[-1]) + jax.lax.dot_general(
        x, decay_states, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_c - 1)
    def _emit_state():
        st_out_ref[0] = state_scr[...].astype(st_out_ref.dtype)


def ssd_hm(x, da, b, c, *, chunk: int, interpret: bool | None = None):
    """Head-major SSD scan. Returns (y (BH,S,P), state (BH,P,N))."""
    bh, s, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0, (s, chunk)
    n_c = s // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, n_c),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, ci: (i, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, ci: (i, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, ci: (i, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, ci: (i, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, ci: (i, ci, 0)),
            pl.BlockSpec((1, p, n), lambda i, ci: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), x.dtype),
            jax.ShapeDtypeStruct((bh, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=backend.interpret(interpret),
    )(x, da, b, c)
