"""Write-your-own accelerator: the XAIF no-fork extension path.

Implements a toy "Keccak-ish" mixing accelerator (the paper's §II-A1 memory-
class example) as a Pallas kernel, registers it through XAIF with slave/
master ports + a power domain, and runs it through the platform dispatcher —
zero changes to platform or model code.

    PYTHONPATH=src python examples/accelerator_plugin.py
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.platform import Platform, XHeepConfig
from repro.core.power import PowerDomain, PowerState
from repro.core.xaif import AcceleratorSpec, PortSpec
from repro.kernels import backend
from repro.sharding.params import Axes


# --- 1. the kernel (compute unit) -------------------------------------------

def _mix_kernel(x_ref, o_ref):
    x = x_ref[0].astype(jnp.uint32)
    # a few rounds of xor-rotate mixing (keccak-flavoured, not cryptographic)
    for r in range(4):
        rot = jnp.bitwise_or(jnp.left_shift(x, 7), jnp.right_shift(x, 25))
        x = jnp.bitwise_xor(x, rot) + jnp.uint32(0x9E3779B9 + r)
    o_ref[0] = x


def mix(x: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    b, n = x.shape
    return pl.pallas_call(
        _mix_kernel,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.uint32),
        interpret=backend.interpret(interpret),
    )(x)


def mix_ref(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.uint32)
    for r in range(4):
        rot = jnp.bitwise_or(jnp.left_shift(x, 7), jnp.right_shift(x, 25))
        x = jnp.bitwise_xor(x, rot) + jnp.uint32(0x9E3779B9 + r)
    return x


# --- 2. the XAIF contract -----------------------------------------------------

SPEC = AcceleratorSpec(
    name="keccakish_mixer",
    op="mix",
    impl="pallas",
    fn=mix,
    slave_ports=(PortSpec("ctrl_status", Axes(), direction="slave",
                          dtype="int32"),
                 PortSpec("data_mem", Axes(None, None), direction="slave",
                          dtype="uint32")),
    master_ports=(PortSpec("dma_stream", Axes(None, None)),),
    power_domain=PowerDomain("keccak", leak_uw=4.0, active_dyn_uw_mhz=18.0),
    description="2-slave-port memory-class accelerator (paper §II-A1)",
)


def main():
    platform = Platform(XHeepConfig(core="cv32e20"))
    platform.attach(SPEC)   # <- the whole integration effort
    print("attached:", SPEC.name, "| power domains:",
          sorted(platform.power.domains))

    x = jnp.asarray(np.random.default_rng(0).integers(0, 2**32, (4, 128),
                                                      dtype=np.uint32))
    got = platform.dispatch("mix", x)
    want = mix_ref(x)
    assert (np.asarray(got) == np.asarray(want)).all()
    print("accelerator output matches host reference on",
          x.shape, "uint32 block")

    # interrupt + power-gate after completion, like the paper's CGRA flow
    platform.power.set_state("keccak", PowerState.OFF)
    print("keccak domain gated; platform leakage:",
          platform.power.leakage_uw(), "uW")


if __name__ == "__main__":
    main()
