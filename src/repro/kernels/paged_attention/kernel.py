"""Paged decode attention Pallas TPU kernel (single query, GQA, block table).

The decode-side analogue of the flash kernel in ``kernels/attention``: one
query per slot attends over that slot's KV, but the KV lives *in place* in a
global page pool — fixed-size pages of ``page_size`` positions — reached
through a per-slot block table instead of a contiguous per-slot lane. The
block table rides in as a scalar-prefetch operand
(:class:`pltpu.PrefetchScalarGridSpec`), so the page id is known before the
kernel body runs and each grid step DMA-streams exactly one pool page
HBM->VMEM; nothing is ever copied into a per-slot contiguous buffer (the
VWR2A "operate on data where it already sits" discipline).

Grid = (slots, n_pages); the page dimension is innermost and sequential, and
the running (m, l, acc) online-softmax state is carried across it in VMEM
scratch, exactly like the flash kernel carries its KV-tile loop.

Layout contract: q (B, H, D); k/v pool (P', page_size, K·D) — the flat,
lane-dense page space of the serving arena, each position's K heads
head-major along the lanes (head ``kh`` at ``[kh·D, (kh+1)·D)``); tables
(B, NP) int32 rows of that space; lengths (B,) int32 valid-position counts.
The arena holds every layer's pages in one space (layer ``l``'s page ``p``
is row ``l·P + p``), so the caller picks the layer by offsetting the
tables by ``l·P`` and the kernel reads the arena where it lies, with no
per-layer slice. H and D come from q and K = lanes // D from the pool, so
the kernel serves a tensor-parallel head slice (H/tp, K/tp inside
``shard_map``) exactly like the full head set.

GQA is folded head-major: head h reads KV head ``h // (H // K)``. Each
grid step streams one page of all K heads as a ``(page_size, K·D)`` block
and attends it with a **block-diagonal** query: row h of the (H, K·D)
query holds q[h] in its KV head's lanes and zeros elsewhere, so one
(H, K·D) x (K·D, page_size) matmul gives every head's scores against its
own KV head, exactly. ``p @ v`` then gives (H, K·D), of which each head
keeps its own head's lanes; the wrapper takes that diagonal after the
call. The zeros cost K times the MXU work of a page, which is small
against the per-step cost at decode's one query a slot.

With ``window`` set the table is a **ring block table** (the sliding-window
serving layout): entry ``e`` holds the page of the newest block
``b ≡ e (mod NP)`` at or below the tail block — older same-entry blocks
have been recycled because their positions fall wholly outside the window,
so a slot's table needs only ``ceil(window/page_size) + 1`` entries no
matter how long the sequence runs. The kernel still streams one page per
grid step; it just derives each entry's absolute positions from the ring
mapping and masks to ``[kv_len - window, kv_len)``. A full-width
contiguous table is the degenerate ring (no entry reused), so the same
code path serves both layouts.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

NEG_INF = -1e30


def _paged_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page_size: int,
                  window: int | None, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_p = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = lengths_ref[b]
    q = q_ref[0]                                   # (H, K·D) block-diagonal
    k = k_ref[0]                                   # (ps, K·D)
    # bf16 products are exact in f32, so scores accumulate in f32 as if
    # the operands had been widened first
    dt = jnp.promote_types(q.dtype, k.dtype)
    s = lax.dot_general(q.astype(dt), k.astype(dt), (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale  # (H,ps)

    if window is not None:
        # ring block table: entry j holds the newest block b ≡ j (mod n_p)
        # with b <= (kv_len-1)//ps — recycled (older) blocks fall wholly
        # outside the window, so positions derive from that block index
        cur = (kv_len - 1) // page_size
        blk = cur - jnp.mod(cur - j, n_p)
        kpos = blk * page_size + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (kpos >= 0) & (kpos < kv_len) & (kpos >= kv_len - window)
    else:
        kpos = j * page_size + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_len
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                            # (H, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    pv = jnp.dot(p, v_ref[0].astype(jnp.float32),
                 preferred_element_type=jnp.float32)         # (H, K·D)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = m_new

    @pl.when(j == n_p - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_attention_kernel(q, k_pool, v_pool, tables, lengths, *,
                           window: int | None = None,
                           scale: float | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """Fused paged single-query attention (see module docstring for layout).

    Returns o (B, H, D). ``scale`` defaults to ``1/sqrt(D)`` — pass the
    unpadded head dim's scale explicitly when D is padded for the MXU.
    """
    b, h, d = q.shape
    _, ps, lanes = k_pool.shape
    kh = lanes // d
    assert kh * d == lanes and h % kh == 0, (h, d, lanes)
    groups = h // kh
    np_per_slot = tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    # row h carries q[h] in the lanes of its KV head h // groups
    eye = jnp.eye(kh, dtype=q.dtype)
    q_bd = jnp.einsum("bkgd,kj->bkgjd", q.reshape(b, kh, groups, d),
                      eye).reshape(b, h, lanes)

    kernel = functools.partial(_paged_kernel, page_size=ps, window=window,
                               scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, np_per_slot),
        in_specs=[
            pl.BlockSpec((1, h, lanes), lambda bi, j, t, ln: (bi, 0, 0)),
            pl.BlockSpec((1, ps, lanes),
                         lambda bi, j, t, ln: (t[bi, j], 0, 0)),
            pl.BlockSpec((1, ps, lanes),
                         lambda bi, j, t, ln: (t[bi, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, lanes), lambda bi, j, t, ln: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, lanes), jnp.float32),
        ],
    )

    o = pl.pallas_call(
        kernel,
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, lanes), q.dtype),
        interpret=backend.interpret(interpret),
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q_bd, k_pool,
      v_pool)
    # each head keeps the lanes of its own KV head: the block diagonal
    o = o.reshape(b, kh, groups, kh, d)
    return jnp.diagonal(o, axis1=1, axis2=3).transpose(0, 3, 1, 2).reshape(
        b, h, d)
