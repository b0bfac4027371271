"""Ahead-of-time TPU compiles of the paged-attention kernel at real widths.

The TPU compiler is installed even where no TPU is attached, so these
tests lower the Pallas kernel with ``interpret=False`` for a described
``v5e:2x2`` topology and compile it for one of its chips. That catches
what interpret mode cannot: block shapes the TPU tiling refuses, scratch
that does not fit VMEM. Shapes are granite-3-2b's decode widths (8 slots,
32 query / 8 KV heads, head dim 64, 16-token pages, max_len 1024 = 64
table entries): a contiguous table, a 256-token sliding window, and the
per-device head slice of tp=4 (8 / 2 heads).

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports
every test file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import paged_attention_kernel

SLOTS, HEAD_DIM, PAGE, ENTRIES = 8, 64, 16, 64
N_PAGES = SLOTS * ENTRIES + 1


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("heads,kv_heads,window", [
    (32, 8, None),      # contiguous block table
    (32, 8, 256),       # ring table of a 256-token sliding window
    (8, 2, None),       # one device's head slice at tp=4
], ids=["contiguous", "window256", "tp4_slice"])
def test_paged_attention_kernel_compiles_for_v5e(one_chip, heads, kv_heads,
                                                 window):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    entries = ENTRIES if window is None else -(-window // PAGE) + 1
    args = (spec((SLOTS, heads, HEAD_DIM), jnp.bfloat16),
            spec((N_PAGES, PAGE, kv_heads, HEAD_DIM), jnp.bfloat16),
            spec((N_PAGES, PAGE, kv_heads, HEAD_DIM), jnp.bfloat16),
            spec((SLOTS, entries), jnp.int32),
            spec((SLOTS,), jnp.int32))

    def step(q, k, v, tables, lengths):
        return paged_attention_kernel(q, k, v, tables, lengths,
                                      window=window, interpret=False)

    compiled = jax.jit(step).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the call is named, so a profile tells it from any other Pallas call
    assert re.search(r"%paged_attention[.\d]* = .*"
                     r'custom_call_target="tpu_custom_call"', text)
