"""Operations and bytes that the paged-attention kernel needs for one step.

Counted from what the step asks of it, not from what the kernel visits:
each live slot's query attends over its own live tokens, so the kernel
needs the K and V of those tokens at the logical ``head_dim`` (not the
table's full width, nor a layout padded for the tiles), plus q and o, in
each layer that holds KV in the paged arena (the model file's
``attention_layers``). The KV append is an XLA scatter outside the kernel
and is not counted.
"""

from __future__ import annotations

from bench import spec

# substrings of the trace's op labels (``bench/trace.py``) that belong to
# this kernel: the name the program gives its ``pallas_call``, which the
# TPU trace keeps as the custom call's instruction name
# (``%paged_attention.9 custom-call tpu_custom_call``). Another Pallas
# kernel in the step is another custom call and is not counted here.
TRACE_NAMES = ("paged_attention",)


def cost(cfg: dict, queries: int, attended: int, dtype_bytes: int = 2
         ) -> tuple[float, float]:
    """(FLOPs, bytes) over ``queries`` single-token queries that attend
    over ``attended`` key positions in all, in every layer that holds KV."""
    layers = spec.model(cfg).attention_layers(cfg)
    q_width = sum(h * hd for h, _, hd in layers)
    kv_width = sum(kv * hd for _, kv, hd in layers)
    flops = 4.0 * q_width * attended
    kv_bytes = 2.0 * kv_width * attended * dtype_bytes
    qo_bytes = 2.0 * q_width * queries * dtype_bytes
    return flops, kv_bytes + qo_bytes
