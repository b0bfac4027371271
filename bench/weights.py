"""Random weights for a configuration file, made on the device from a seed.

One jitted call draws the whole tree, in the served dtype, so set-up pays
one program (read back from the persistent compile cache after the first
run) and no leaf ever exists in float32 outside that program. The tree,
in the layout the serving engine takes, is the configuration's model file's
(``bench/models/<equations>.py`` ``shapes``).

Each leaf carries its init: an int is a fan-in, drawn N(0, 1/fan_in) over
the contracted axes; ``"embed"`` is N(0, 0.02); ``"norm"`` is 1 + N(0, 0.1);
``("normal", std)`` is N(0, std²); ``"zeros"`` is zero. With that scaling a
40-layer stack stays well conditioned, so a rounding-sized difference in
one layer stays rounding-sized at the logits (see ``PERF.md``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import spec


def shapes(cfg: dict) -> dict:
    """``{path: (shape, fan_in or init tag)}`` for every leaf of the tree."""
    return spec.model(cfg).shapes(cfg)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        *parents, name = path.split("/")
        node = tree
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return tree


def _draw(key, shape, init, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if init == "embed":
        w = z * 0.02
    elif init == "norm":
        w = 1.0 + 0.1 * z
    elif init == "zeros":
        w = jnp.zeros(shape, jnp.float32)
    elif isinstance(init, tuple) and init[0] == "normal":
        w = z * init[1]
    elif isinstance(init, int):
        w = z * (1.0 / math.sqrt(init))
    else:
        raise ValueError(f"unknown init {init!r}")
    return w.astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, leaves: tuple, dtype):
    keys = jax.random.split(key, len(leaves))
    return [_draw(k, shape, init, dtype)
            for k, (_, shape, init) in zip(keys, leaves)]


def make(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole weight tree for ``cfg`` from ``seed``, in one program."""
    leaves = tuple((p, s, i) for p, (s, i) in sorted(shapes(cfg).items()))
    arrays = _make(jax.random.key(seed), leaves, jnp.dtype(dtype))
    return _nest({p: a for (p, _, _), a in zip(leaves, arrays)})
