"""jit wrapper + XAIF registration for the flash attention kernel.

The XAIF contract mirrors the paper's CGRA plug-in: 3 master read ports
(Q, K, V tiles streamed from HBM), 1 master write port (O tiles), slave
ports = the static shape/window configuration; its power domain joins the
platform power manager when attached.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.power import PowerDomain
from repro.core.xaif import AcceleratorSpec, PortSpec, register
from repro.kernels.attention.kernel import flash_attention_hm
from repro.sharding import axes as lx
from repro.sharding.params import Axes


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "q_block", "kv_block", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    q_offset: int = 0, kv_len=None, q_block: int = 128,
                    kv_block: int = 128, interpret: bool | None = None):
    """Batch-seq-major entry: q (B,S,H,D); k/v (B,S,K,D). GQA handled by the
    kernel's block index mapping (no KV materialization)."""
    if kv_len is not None:
        raise NotImplementedError(
            "dynamic kv_len is served by the chunked backend; the Pallas "
            "kernel covers the static train/prefill shapes")
    b, sq, h, d = q.shape
    sk, nkv = k.shape[1], k.shape[2]
    groups = h // nkv
    # head-major + pad: D to 128 (MXU), S to tile multiples
    qh = _pad_to(_pad_to(q.transpose(0, 2, 1, 3).reshape(b * h, sq, d), 2, 128),
                 1, q_block)
    kh = _pad_to(_pad_to(k.transpose(0, 2, 1, 3).reshape(b * nkv, sk, d), 2, 128),
                 1, kv_block)
    vh = _pad_to(_pad_to(v.transpose(0, 2, 1, 3).reshape(b * nkv, sk, d), 2, 128),
                 1, kv_block)
    # scale uses the padded D inside the kernel; compensate so logits match
    d_pad = qh.shape[-1]
    qh = qh * jnp.asarray(d_pad ** 0.5 / d ** 0.5, qh.dtype)
    out = flash_attention_hm(qh, kh, vh, groups=groups, causal=causal,
                             window=window, sq=sq, sk=sk, q_block=q_block,
                             kv_block=kv_block, interpret=interpret)
    out = out[:, :sq, :d].reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return out


SPEC = AcceleratorSpec(
    name="flash_attention_pallas",
    op="attention",
    impl="pallas",
    fn=flash_attention,
    slave_ports=(
        PortSpec("attn_config", Axes(), direction="slave", dtype="int32"),
    ),
    master_ports=(
        PortSpec("q", Axes(lx.BATCH, lx.SEQ, lx.HEADS, lx.HEAD_DIM)),
        PortSpec("k", Axes(lx.BATCH, lx.SEQ, lx.KV_HEADS, lx.HEAD_DIM)),
        PortSpec("v", Axes(lx.BATCH, lx.SEQ, lx.KV_HEADS, lx.HEAD_DIM)),
        PortSpec("o", Axes(lx.BATCH, lx.SEQ, lx.HEADS, lx.HEAD_DIM)),
    ),
    power_domain=PowerDomain("acc_attention", leak_uw=12.0,
                             active_dyn_uw_mhz=48.0),
    description="FlashAttention TPU kernel: online softmax over VMEM KV tiles",
)
register(SPEC, allow_override=True)
