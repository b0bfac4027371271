"""Pure-jax oracle for the paged decode attention kernel.

Semantics (shared with the Pallas kernel in ``kernel.py``): each slot ``b``
holds one single-token query and a *block table* — a row of page ids into a
global KV page pool. The op gathers the slot's pages, masks positions at or
beyond ``lengths[b]``, and computes grouped-query attention. The reference
deliberately reconstructs the slot's KV exactly as the lane-cache engine
lays it out and then runs the very same
:func:`repro.models.layers.attention_chunked` the lane decode path uses —
so the paged engine's decode is *bit-identical* to the PR 2 per-lane
cache, not merely allclose. That holds for both table layouts:

* **Contiguous** (``window=None``): page ``j`` of the table covers
  positions ``[j*ps, (j+1)*ps)`` — the gather reproduces the lane's
  linear cache buffer.
* **Ring** (``window=W``): the table is a *ring block table* with ``R``
  entries; entry ``e`` holds the page of the **newest** block ``b`` with
  ``b ≡ e (mod R)`` and ``b <= (n-1)//ps`` (older same-entry blocks have
  been recycled — their positions fall wholly outside the window). The
  gather reconstructs the lane backend's **ring buffer**: a ``W``-position
  buffer where position ``p`` sits at index ``p % W``, attended over
  ``kv_len = min(n, W)`` — byte-for-byte the layout
  ``transformer._attn_decode`` keeps for sliding-window configs, so
  windowed paged decode is bit-identical to the lane ring cache. A table
  with ``R >= ceil(W/ps) + 1`` entries always covers the window
  (``(R-1)*ps >= W``), which is why a long-running sliding-window slot
  holds O(window) pages instead of O(seq). A full-width contiguous table
  is the degenerate ring (no entry is ever reused), so callers with
  un-recycled tables can pass ``window`` unchanged.

Layout: the pool is **flat and lane-dense**, ``(P', ps, K·D)``: page ``p``
holds ``ps`` positions, each a row of ``K·D`` lanes, head-major (KV head
``kh`` sits at lanes ``[kh·D, (kh+1)·D)``). The serving arena stacks every
layer's pages in one such page space — layer ``l``'s page ``p`` is row
``l·P + p`` — so a caller attends layer ``l`` by offsetting the block table
by ``l·P``; the ops themselves know nothing of layers. ``K`` is read off
the shapes, ``pool.shape[-1] // q.shape[-1]``.

Tensor parallelism: every head count here is read off the operand shapes
(``H`` from q, ``K`` from the pool's lanes; GQA groups = H // K), never
from a config — so the same code runs unchanged inside ``shard_map`` on a
per-device head slice (H/tp query heads against a pool arena holding the
lanes of Kh/tp KV heads). Each query head attends only to its own KV
head, so a head slice's output block is bitwise the same rows of the
full-H result; the serving layer all-gathers the blocks before the output
projection (see ``transformer.decode_step_paged``). The Pallas kernel
shares the shape-polymorphic contract, keeping ref/pallas parity checks
valid per device slice too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import attention_chunked


def paged_attention(q, k_pool, v_pool, tables, lengths, *,
                    window: int | None = None) -> jax.Array:
    """Single-query paged attention, pure-jax reference.

    q: (B, H, D) — one post-rope query per slot.
    k_pool/v_pool: (P', ps, K·D) — the flat page space, head-major lanes
        (the tables pick the layer's rows; see the module docstring).
    tables: (B, NP) int32 — page ids per slot; unused entries must point at
        pages whose positions are masked (beyond ``lengths[b]``, or outside
        the window), so their contents are never observable.
    lengths: (B,) int32 — valid KV entries per slot; attention covers
        positions ``[0, lengths[b])`` (clipped to the window).
    window: optional sliding window — only the last ``window`` positions
        attend (the query sits at position ``lengths[b] - 1``), and the
        table is read with **ring** semantics (see the module docstring).
        Both paths reconstruct the lane engine's exact cache layout (linear
        buffer / ring buffer) and run the same ``attention_chunked``, so
        either way the result is bit-identical to the lane decode.
    """
    d = q.shape[-1]
    _, ps, lanes = k_pool.shape
    kh = lanes // d
    n_entries = tables.shape[1]

    def one(qb, tb, lb):
        if window is not None:
            # lane ring layout: buffer index i holds the newest position
            # p < lb with p ≡ i (mod window); kv_len clips the cold start
            i = jnp.arange(window)
            p = i + ((lb - 1 - i) // window) * window
            p = jnp.maximum(p, 0)          # i >= lb lanes: masked by kv_len
            entry = (p // ps) % n_entries  # ring block-table mapping
            kg = k_pool[tb[entry], p % ps].reshape(window, kh, d)
            vg = v_pool[tb[entry], p % ps].reshape(window, kh, d)
            kv_len = jnp.minimum(lb, window)
            return attention_chunked(qb[None, None], kg[None], vg[None],
                                     causal=False, kv_len=kv_len)[0, 0]
        kg = k_pool[tb].reshape(1, -1, kh, d)
        vg = v_pool[tb].reshape(1, -1, kh, d)
        return attention_chunked(qb[None, None], kg, vg, causal=False,
                                 kv_len=lb)[0, 0]

    return jax.vmap(one)(q, tables, lengths)


def append_to_tail_pages(k_new, v_new, k_pool, v_pool, tables, lengths,
                         append_mask=None):
    """Scatter each slot's new KV entry into its tail page, in place.

    The entry (``k_new[b]`` of shape (K, D), stored as its K·D head-major
    lanes) lands at page ``tables[b, (lengths[b] // ps) % NP]``, row
    ``lengths[b] % ps`` — the ``% NP`` makes the same code serve contiguous
    tables (where ``lengths // ps < NP`` always) and ring tables (where the
    tail block's entry wraps). ``append_mask`` (B,) bool drops masked
    lanes' writes by pointing them at the out-of-range page index
    (``mode="drop"`` — the pool is untouched bitwise). Shared by the ref
    and pallas dispatch paths so the append semantics cannot diverge
    between them.
    """
    n_pages, ps = k_pool.shape[0], k_pool.shape[1]
    b = k_new.shape[0]
    page = tables[jnp.arange(b), (lengths // ps) % tables.shape[1]]
    off = lengths % ps
    if append_mask is not None:
        page = jnp.where(append_mask, page, n_pages)
    lanes = k_pool.shape[-1]
    k_pool = k_pool.at[page, off].set(
        k_new.reshape(b, lanes).astype(k_pool.dtype), mode="drop")
    v_pool = v_pool.at[page, off].set(
        v_new.reshape(b, lanes).astype(v_pool.dtype), mode="drop")
    return k_pool, v_pool

