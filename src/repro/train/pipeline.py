"""GPipe-style pipeline parallelism over a mesh axis via collective_permute.

Optional PP for very deep stacks: layers are grouped into S stages, one per
device along the ``stage`` mesh axis; microbatches stream through with the
classic (S - 1)-bubble schedule. Activations move stage-to-stage with
``lax.ppermute`` inside ``shard_map`` — the jax-native rendition of the
send/recv pipeline, with no torch.distributed emulation.

The implementation is schedule-only (forward streaming + loss on the last
stage); it composes with grad accumulation by treating each microbatch slot
as a pipeline slot.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_forward(stage_fn: Callable, mesh: Mesh, axis: str = "stage"):
    """Build ``run(stage_params, x_microbatches) -> y_microbatches``.

    stage_fn(params_local, x) applies ONE stage's layers.
    stage_params leaves: (S, ...) — stacked per stage, sharded over ``axis``.
    x_microbatches: (M, mb, ...) — every microbatch visits every stage.
    """
    s = mesh.shape[axis]

    def local(stage_params, xs):
        stage_params = jax.tree.map(lambda a: a[0], stage_params)
        idx = lax.axis_index(axis)
        m = xs.shape[0]
        n_ticks = m + s - 1
        perm = [(i, (i + 1) % s) for i in range(s)]

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 ingests microbatch t (if any); others take the permuted
            # activation from the previous stage
            x_in = jnp.where(t < m, xs[jnp.minimum(t, m - 1)], jnp.zeros_like(xs[0]))
            inp = jnp.where(idx == 0, x_in, buf)
            out = stage_fn(stage_params, inp)
            # last stage emits out for microbatch (t - (S-1))
            emit_t = t - (s - 1)
            outputs = lax.cond(
                (emit_t >= 0) & (idx == s - 1),
                lambda o: o.at[jnp.maximum(emit_t, 0)].set(out),
                lambda o: o, outputs)
            buf = lax.ppermute(out, axis, perm)
            return (buf, outputs), None

        buf0 = jnp.zeros_like(xs[0])
        out0 = jnp.zeros_like(xs)
        (_, outputs), _ = lax.scan(tick, (buf0, out0), jnp.arange(n_ticks))
        # broadcast final outputs from the last stage to all (masked psum —
        # ppermute wants a bijection, a broadcast is not one)
        outputs = lax.psum(jnp.where(idx == s - 1, outputs, 0.0), axis)
        return outputs

    in_specs = (P(axis), P())
    out_specs = P()
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)
