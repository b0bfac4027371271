"""jit wrapper + XAIF registration for the conv1d "CGRA" accelerator.

Port structure intentionally mirrors the paper's CGRA (§IV-A2): two slave
ports (configuration registers + context memory = the tap weights) and four
master ports (the 4 PEs' independent HBM streams ≙ 4×32 bit OBI masters,
128 bit/cycle); one interrupt line (completion callback); one power-control
port (the `cgra` power domain registered with the platform power manager).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.power import PowerDomain
from repro.core.xaif import AcceleratorSpec, PortSpec, register
from repro.kernels.conv1d.kernel import conv1d_causal
from repro.sharding import axes as lx
from repro.sharding.params import Axes


def _pick_block(n, pref):
    for bbb in (pref, 128, 64, 32, 16, 8, 4, 2, 1):
        if bbb <= n and n % bbb == 0:
            return bbb
    return 1


@functools.partial(jax.jit, static_argnames=("interpret",))
def conv1d(x, w, *, interpret: bool | None = None):
    """x: (B,S,D), w: (W,D) -> (B,S,D) causal depthwise conv."""
    b, s, d = x.shape
    sb = _pick_block(s, 256)
    if sb < w.shape[0] - 1:
        sb = s  # tiny sequences: single block
    return conv1d_causal(x, w, s_block=sb, d_block=_pick_block(d, 128),
                         interpret=interpret)


SPEC = AcceleratorSpec(
    name="cgra_conv1d_pallas",
    op="conv1d",
    impl="pallas",
    fn=conv1d,
    slave_ports=(
        PortSpec("config_regs", Axes(), direction="slave", dtype="int32"),
        PortSpec("context_memory", Axes(lx.CONV, lx.RNN_WIDTH), direction="slave"),
    ),
    master_ports=(
        PortSpec("pe0_stream", Axes(lx.BATCH, lx.SEQ, lx.RNN_WIDTH)),
        PortSpec("pe1_stream", Axes(lx.BATCH, lx.SEQ, lx.RNN_WIDTH)),
        PortSpec("pe2_stream", Axes(lx.BATCH, lx.SEQ, lx.RNN_WIDTH)),
        PortSpec("pe3_stream", Axes(lx.BATCH, lx.SEQ, lx.RNN_WIDTH)),
    ),
    power_domain=PowerDomain("cgra", leak_uw=15.0, active_dyn_uw_mhz=54.63,
                             retainable=False),
    description="CGRA-analogue depthwise conv: taps unrolled, lanes as PEs",
)
register(SPEC, allow_override=True)
