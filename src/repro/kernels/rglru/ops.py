"""jit wrapper + XAIF registration for the RG-LRU scan kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.power import PowerDomain
from repro.core.xaif import AcceleratorSpec, PortSpec, register
from repro.kernels.rglru.kernel import rglru_scan
from repro.sharding import axes as lx
from repro.sharding.params import Axes


@functools.partial(jax.jit, static_argnames=("interpret",))
def rglru(a, b, h0=None, *, interpret: bool | None = None):
    """a, b: (B,S,W) -> (ys (B,S,W), h_final (B,W))."""
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0.astype(b.dtype))
    return rglru_scan(a.astype(jnp.float32), b.astype(jnp.float32),
                      interpret=interpret)


SPEC = AcceleratorSpec(
    name="rglru_scan_pallas",
    op="rglru",
    impl="pallas",
    fn=rglru,
    master_ports=(
        PortSpec("a", Axes(lx.BATCH, lx.SEQ, lx.RNN_WIDTH)),
        PortSpec("b", Axes(lx.BATCH, lx.SEQ, lx.RNN_WIDTH)),
        PortSpec("y", Axes(lx.BATCH, lx.SEQ, lx.RNN_WIDTH)),
    ),
    power_domain=PowerDomain("acc_rglru", leak_uw=6.0, active_dyn_uw_mhz=22.0),
    description="RG-LRU linear scan: width on vector lanes, VMEM state",
)
register(SPEC, allow_override=True)
