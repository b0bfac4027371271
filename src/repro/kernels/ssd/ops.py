"""jit wrapper + XAIF registration for the SSD chunk-scan kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.power import PowerDomain
from repro.core.xaif import AcceleratorSpec, PortSpec, register
from repro.kernels.ssd.kernel import ssd_hm
from repro.sharding import axes as lx
from repro.sharding.params import Axes


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dA, B, C, *, chunk: int, init_state=None, interpret: bool | None = None):
    """Model-layout entry: x (b,s,h,p), dA (b,s,h), B/C (b,s,h,n) ->
    (y (b,s,h,p), state (b,h,p,n))."""
    if init_state is not None:
        raise NotImplementedError("init_state continuation uses the chunked backend")
    b, s, h, p = x.shape
    n = B.shape[-1]

    def hm(a, feat):
        return a.transpose(0, 2, 1, 3).reshape(b * h, s, feat)

    y, state = ssd_hm(hm(x, p), dA.transpose(0, 2, 1).reshape(b * h, s, 1),
                      hm(B, n), hm(C, n), chunk=min(chunk, s),
                      interpret=interpret)
    y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    return y, state.reshape(b, h, p, n)


SPEC = AcceleratorSpec(
    name="ssd_chunk_scan_pallas",
    op="ssd",
    impl="pallas",
    fn=ssd,
    slave_ports=(PortSpec("chunk_config", Axes(), direction="slave",
                          dtype="int32"),),
    master_ports=(
        PortSpec("x", Axes(lx.BATCH, lx.SEQ, lx.HEADS, lx.HEAD_DIM)),
        PortSpec("dA", Axes(lx.BATCH, lx.SEQ, lx.HEADS)),
        PortSpec("B", Axes(lx.BATCH, lx.SEQ, lx.HEADS, lx.STATE)),
        PortSpec("C", Axes(lx.BATCH, lx.SEQ, lx.HEADS, lx.STATE)),
        PortSpec("y", Axes(lx.BATCH, lx.SEQ, lx.HEADS, lx.HEAD_DIM)),
    ),
    power_domain=PowerDomain("acc_ssd", leak_uw=9.0, active_dyn_uw_mhz=40.0),
    description="SSD chunk scan: MXU intra-chunk, VMEM-resident state",
)
register(SPEC, allow_override=True)
