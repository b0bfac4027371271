"""Dispatch + XAIF registration for the paged decode attention kernel.

``paged_decode_append`` is the engine-facing fused op: scatter the step's
new K/V entry into each slot's tail page (an in-place update on the donated
pool buffers), then run single-query attention *directly against the page
pool* through the block table. Two backends:

* ``impl="ref"`` — the pure-jax oracle (``ref.py``). Its gather + masked
  attention is arranged to be bit-identical to the PR 2 lane-cache decode,
  so it is what the engine runs off the TPU: paged serving changes memory
  layout, never tokens.
* ``impl="pallas"`` — the fused TPU kernel (``kernel.py``): block-table
  scalar prefetch, one pool page streamed per grid step, online softmax in
  VMEM scratch. The engine runs it whenever JAX runs on a TPU, compiled
  (``repro.kernels.backend`` decides interpret mode from the backend). The
  append scatter is an XLA in-place update on the donated pool under both
  backends.

The pools are the flat, lane-dense page space ``(P', ps, K·D)`` of the
serving arena (K heads head-major along the lanes). The arena holds every
layer's pages — layer ``l``'s page ``p`` is row ``l·P + p`` — and the
caller hands the ops tables offset by ``l·P``; the ops see one page space
and know nothing of layers, so the append scatters into the whole donated
arena in place and the kernel reads it there.

The append and the attention run under the named scopes ``attn.append``
and ``attn.kernel``, and the Pallas call is named ``paged_attention``, so
the compiled step's op metadata says which code each device op came from.

The XAIF contract mirrors the paper's CGRA plug-in: master read ports for
the query and the two pool planes plus the block table, one master write
port for O, slave ports = the static page-size/window configuration.
"""

from __future__ import annotations

import jax

from repro.core.power import PowerDomain
from repro.core.xaif import AcceleratorSpec, PortSpec, register
from repro.kernels.paged_attention import ref
from repro.kernels.paged_attention.kernel import paged_attention_kernel
from repro.sharding import axes as lx
from repro.sharding.params import Axes


def paged_attention(q, k_pool, v_pool, tables, lengths, *,
                    window: int | None = None, impl: str = "ref",
                    interpret: bool | None = None):
    """Single-query paged attention over a flat (P', ps, K·D) page space.

    q (B, H, D); tables (B, NP) int32 rows of the page space (layer ``l``
    of the arena: page ids offset by ``l·P``); lengths (B,) valid counts.
    With ``window`` the tables carry **ring** semantics — entry ``e`` holds
    the newest block ``b ≡ e (mod NP)`` — and only the last ``window``
    positions attend; see the ``kernel.py``/``ref.py`` module docstrings.
    """
    if impl == "pallas":
        return paged_attention_kernel(q, k_pool, v_pool, tables, lengths,
                                      window=window, interpret=interpret)
    if impl == "ref":
        return ref.paged_attention(q, k_pool, v_pool, tables, lengths,
                                   window=window)
    raise ValueError(f"unknown paged_attention impl {impl!r}")


def paged_decode_append(q, k_new, v_new, k_pool, v_pool, tables, lengths, *,
                        append_mask=None, window: int | None = None,
                        impl: str = "ref", interpret: bool | None = None):
    """Fused decode step: append the new KV entry, attend over it in place.

    Appends ``k_new[b]``/``v_new[b]`` (B, K, D), as K·D head-major lanes,
    at position ``lengths[b]`` of slot ``b``'s page chain in the flat pools
    (P', ps, K·D) (``append_mask`` False drops the append — the lane is
    riding the batch idle and its output is ignored), then attends over
    ``lengths[b] + 1`` positions. With ``window`` the block tables are ring
    tables (the tail entry wraps modulo the table width) and attention
    covers only the last ``window`` positions — bit-identical to the lane
    backend's ring cache. Returns ``(o, k_pool', v_pool')`` — pass donated
    pools so XLA updates them in place.
    """
    with jax.named_scope("attn.append"):
        k_pool, v_pool = ref.append_to_tail_pages(
            k_new, v_new, k_pool, v_pool, tables, lengths, append_mask)
    with jax.named_scope("attn.kernel"):
        o = paged_attention(q, k_pool, v_pool, tables, lengths + 1,
                            window=window, impl=impl, interpret=interpret)
    return o, k_pool, v_pool


SPEC = AcceleratorSpec(
    name="paged_attention_pallas",
    op="paged_attention",
    impl="pallas",
    fn=paged_attention_kernel,
    slave_ports=(
        PortSpec("paged_config", Axes(), direction="slave", dtype="int32"),
    ),
    master_ports=(
        PortSpec("q", Axes(lx.DECODE_BATCH, lx.HEADS, lx.HEAD_DIM)),
        # the flat arena's lanes are KV heads x head_dim, head-major
        PortSpec("k_pool", Axes(None, lx.CACHE_SEQ, lx.KV_HEADS)),
        PortSpec("v_pool", Axes(None, lx.CACHE_SEQ, lx.KV_HEADS)),
        PortSpec("block_table", Axes(lx.DECODE_BATCH, None), dtype="int32"),
        PortSpec("o", Axes(lx.DECODE_BATCH, lx.HEADS, lx.HEAD_DIM)),
    ),
    power_domain=PowerDomain("acc_paged_attention", leak_uw=10.0,
                             active_dyn_uw_mhz=42.0),
    description=("Paged decode attention: block-table scalar prefetch, one "
                 "pool page per grid step, online softmax in VMEM scratch"),
)
register(SPEC, allow_override=True)
