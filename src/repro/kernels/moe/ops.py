"""jit wrapper + XAIF registration for the MoE grouped-matmul kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.power import PowerDomain
from repro.core.xaif import AcceleratorSpec, PortSpec, register
from repro.kernels.moe.kernel import grouped_matmul
from repro.sharding import axes as lx
from repro.sharding.params import Axes


def _blocks_for(c, d, f):
    def pick(n, pref):
        for b in (pref, 128, 64, 32, 16, 8, 4, 2, 1):
            if b <= n and n % b == 0:
                return b
        return 1

    return dict(c_block=pick(c, 128), f_block=pick(f, 128), d_block=pick(d, 256))


@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def moe_ffn(xg, p, kind: str = "swiglu", *, interpret: bool | None = None):
    """xg: (E, C, D); p: expert weights {w_gate, w_up, w_down} (E,...)."""
    e, c, d = xg.shape
    f = p["w_gate"].shape[-1]
    kw = dict(_blocks_for(c, d, f), interpret=interpret)
    gate = grouped_matmul(xg, p["w_gate"].astype(xg.dtype), **kw)
    up = grouped_matmul(xg, p["w_up"].astype(xg.dtype), **kw)
    act = jax.nn.silu(gate) if kind == "swiglu" else jax.nn.gelu(gate)
    kw2 = dict(_blocks_for(c, f, d), interpret=interpret)
    return grouped_matmul((act * up).astype(xg.dtype),
                          p["w_down"].astype(xg.dtype), **kw2)


SPEC = AcceleratorSpec(
    name="moe_grouped_matmul_pallas",
    op="moe_ffn",
    impl="pallas",
    fn=moe_ffn,
    slave_ports=(PortSpec("routing_config", Axes(), direction="slave",
                          dtype="int32"),),
    master_ports=(
        PortSpec("tokens_in", Axes(lx.EXPERT, None, lx.EMBED)),
        PortSpec("w_gate", Axes(lx.EXPERT, lx.EMBED, lx.MLP)),
        PortSpec("w_up", Axes(lx.EXPERT, lx.EMBED, lx.MLP)),
        PortSpec("w_down", Axes(lx.EXPERT, lx.MLP, lx.EMBED)),
        PortSpec("tokens_out", Axes(lx.EXPERT, None, lx.EMBED)),
    ),
    power_domain=PowerDomain("acc_moe", leak_uw=14.0, active_dyn_uw_mhz=52.0),
    description="Expert-grid MXU matmul; unrouted experts stay power-gated",
)
register(SPEC, allow_override=True)
