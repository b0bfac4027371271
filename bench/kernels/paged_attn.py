"""Operations and bytes that the paged-attention kernel needs for one step.

Counted from what the step asks of it, not from what the kernel visits:
each live slot's query attends over its own live tokens, so the kernel
needs the K and V of those tokens at the logical ``head_dim`` (not the
table's full width, nor a layout padded for the tiles), plus q and o.
The KV append is an XLA scatter outside the kernel and is not counted.
"""

from __future__ import annotations

from bench.spec import head_dim

# substrings of the trace's op labels (``bench/trace.py``) that belong to
# this kernel. The TPU trace names a Pallas call only by its custom-call
# target, and the paged step holds no other Pallas kernel.
TRACE_NAMES = ("tpu_custom_call",)


def cost(cfg: dict, queries: int, attended: int, dtype_bytes: int = 2
         ) -> tuple[float, float]:
    """(FLOPs, bytes) over ``queries`` single-token queries that attend
    over ``attended`` key positions in all, every layer."""
    L, h = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    kv, hd = cfg["num_key_value_heads"], head_dim(cfg)
    flops = 4.0 * L * h * hd * attended
    kv_bytes = 2.0 * L * kv * hd * attended * dtype_bytes
    qo_bytes = 2.0 * L * h * hd * queries * dtype_bytes
    return flops, kv_bytes + qo_bytes
