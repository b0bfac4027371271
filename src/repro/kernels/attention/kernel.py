"""Flash attention Pallas TPU kernel (causal, GQA, optional sliding window).

TPU mapping of the FlashAttention insight: online softmax over KV tiles with
the running (m, l, acc) state carried in VMEM scratch across the innermost
(sequential) grid dimension; Q/K/V tiles are streamed HBM->VMEM by BlockSpecs.
MXU alignment: the ops wrapper pads head_dim to a multiple of 128 and the
sequence to tile multiples; tile edges default to 128 (8-sublane aligned).

Layout contract (head-major): q (BH, Sq, D), k/v (BKV, Sk, D), BH = BKV*groups.
Grid = (BH, n_q, n_k); n_k is the innermost, sequential dimension.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int | None,
                 q_block: int, kv_block: int, sq: int, sk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # (bq, D)
    k = k_ref[0].astype(jnp.float32)            # (bk, D)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = (qpos < sq) & (kpos < sk)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_hm(q, k, v, *, groups: int, causal: bool = True,
                       window: int | None = None, sq: int | None = None,
                       sk: int | None = None, q_block: int = 128,
                       kv_block: int = 128, interpret: bool | None = None):
    """Head-major flash attention (see module docstring for layout)."""
    bh, sq_pad, d = q.shape
    bkv, sk_pad, _ = k.shape
    assert bh == bkv * groups, (bh, bkv, groups)
    sq = sq if sq is not None else sq_pad
    sk = sk if sk is not None else sk_pad
    q_block = min(q_block, sq_pad)
    kv_block = min(kv_block, sk_pad)
    assert sq_pad % q_block == 0 and sk_pad % kv_block == 0
    n_q, n_k = sq_pad // q_block, sk_pad // kv_block
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        q_block=q_block, kv_block=kv_block, sq=sq, sk=sk)

    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, q_block, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, kv_block, d),
                         lambda b, qi, ki, g=groups: (b // g, ki, 0)),
            pl.BlockSpec((1, kv_block, d),
                         lambda b, qi, ki, g=groups: (b // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, d), jnp.float32),
        ],
        interpret=backend.interpret(interpret),
    )(q, k, v)
