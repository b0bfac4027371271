"""int8 gradient compression with error feedback for the cross-pod reduce.

The cross-pod DP all-reduce is the lowest-bandwidth collective in the
(2,16,16) mesh (inter-pod links). Quantizing gradients to int8 with a
per-tensor scale cuts its wire bytes 4× vs fp32 (2× vs bf16); the residual
(quantization error) is fed back into the next step's gradients, which keeps
SGD-style convergence (error-feedback compression, Seide et al. / Karimireddy
et al.).

``compressed_psum`` runs inside a ``shard_map`` manual region over the
``pod`` axis with ``data``/``model`` left on auto — model code inside is
untouched (GSPMD still partitions it), only the pod reduction is hand-rolled.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


F32 = jnp.float32


def quantize(g: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8. Returns (q, scale)."""
    amax = jnp.max(jnp.abs(g.astype(F32)))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(g.astype(F32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(F32) * scale


def compressed_psum_tree(grads: Any, error: Any, axis: str) -> tuple[Any, Any]:
    """Inside a shard_map manual region: int8-quantized psum over ``axis``
    with error feedback. Returns (reduced fp32 grads, new error state)."""
    n = jax.lax.axis_size(axis)

    def one(g, e):
        g = g.astype(F32) + e.astype(F32)       # apply feedback
        # agree on ONE scale across the axis (scalar pmax), then the int8
        # payloads are commensurable and can be summed on the wire
        amax = lax.pmax(jnp.max(jnp.abs(g)), axis)
        scale = jnp.maximum(amax, 1e-12) / 127.0
        q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
        err = g - dequantize(q, scale)          # what the wire loses
        q_sum = lax.psum(q.astype(jnp.int32), axis)
        reduced = q_sum.astype(F32) * scale / n
        return reduced, err.astype(e.dtype)

    out = jax.tree.map(one, grads, error)
    red = jax.tree.map(lambda t: t[0], out,
                       is_leaf=lambda x: isinstance(x, tuple))
    new_err = jax.tree.map(lambda t: t[1], out,
                           is_leaf=lambda x: isinstance(x, tuple))
    return red, new_err


def init_error_state(params: Any, dtype=jnp.bfloat16) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, dtype), params)


def make_pod_compressed_grad_fn(grad_fn, mesh: Mesh):
    """Wrap ``grad_fn(params, batch) -> grads`` so each pod computes grads on
    its own batch shard and the pods exchange int8-compressed sums.

    Requires the mesh to have a 'pod' axis; params replicated across pods.
    """
    if "pod" not in mesh.axis_names:
        raise ValueError("pod-compressed gradients need a 'pod' mesh axis")
    auto = frozenset(a for a in mesh.axis_names if a != "pod")

    def fn(params, batch, error):
        def inner(params, batch, error):
            grads = grad_fn(params, batch)
            red, new_err = compressed_psum_tree(grads, error, "pod")
            return red, new_err

        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(), P("pod"), P()),
            out_specs=(P(), P()),
            check_vma=False,
            auto=auto,
        )(params, batch, error)

    return fn
