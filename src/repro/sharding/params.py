"""Parameter declaration: one source of truth for shape, logical axes, init.

A model is declared as a pytree of :class:`ParamDecl`; from that single tree we
derive (a) materialized parameters, (b) the logical-axes tree used by the rule
engine, and (c) abstract ShapeDtypeStructs for the dry-run. This guarantees
the three views can never drift apart.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class Axes:
    """Logical axis names of one array. Deliberately NOT a pytree container so
    axes trees keep the same treedef as parameter trees."""

    __slots__ = ("dims",)

    def __init__(self, *dims: str | None):
        if len(dims) == 1 and isinstance(dims[0], (tuple, list)):
            dims = tuple(dims[0])
        self.dims = tuple(dims)

    def __iter__(self):
        return iter(self.dims)

    def __len__(self):
        return len(self.dims)

    def __getitem__(self, i):
        return self.dims[i]

    def __eq__(self, other):
        return isinstance(other, Axes) and self.dims == other.dims

    def __hash__(self):
        return hash(self.dims)

    def __repr__(self):
        return f"Axes{self.dims}"

    def prepend(self, name: str | None) -> "Axes":
        return Axes(name, *self.dims)


def is_axes(x) -> bool:
    return isinstance(x, Axes)


def _standard_normal(key: jax.Array, shape) -> jax.Array:
    # the barrier keeps XLA from folding a caller's scale into the draw's
    # own constants, so a leaf rounds the same inside a jit as eagerly
    return jax.lax.optimization_barrier(
        jax.random.normal(key, shape, jnp.float32))


@dataclasses.dataclass(frozen=True)
class ParamDecl:
    """Declaration of a single parameter tensor."""

    shape: tuple[int, ...]
    axes: Axes
    init: str = "normal"       # normal | zeros | ones | scaled(normal/fan_in) | embed
    scale: float | None = None  # explicit std for "normal"
    dtype: Any = jnp.float32

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"decl rank mismatch: {self.shape} vs {self.axes}")

    def abstract(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)

    def materialize(self, key: jax.Array) -> jax.Array:
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        if self.init == "normal":
            std = self.scale if self.scale is not None else 0.02
            return (_standard_normal(key, self.shape) * std).astype(self.dtype)
        if self.init == "fan_in":
            fan_in = self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]
            std = 1.0 / math.sqrt(max(fan_in, 1))
            return (_standard_normal(key, self.shape) * std).astype(self.dtype)
        raise ValueError(f"unknown init {self.init}")

    def stacked(self, n: int, axis_name: str | None = None) -> "ParamDecl":
        return dataclasses.replace(
            self, shape=(n, *self.shape), axes=self.axes.prepend(axis_name)
        )


def is_decl(x) -> bool:
    return isinstance(x, ParamDecl)


def map_decls(fn: Callable[[ParamDecl], Any], tree: Any) -> Any:
    return jax.tree.map(fn, tree, is_leaf=is_decl)


def stack_tree(tree: Any, n: int, axis_name: str | None = None) -> Any:
    """Stack every decl in a layer tree n times (scan-over-layers weights)."""
    return map_decls(lambda d: d.stacked(n, axis_name), tree)


def abstract_tree(tree: Any) -> Any:
    return map_decls(lambda d: d.abstract(), tree)


def axes_tree(tree: Any) -> Any:
    return map_decls(lambda d: d.axes, tree)


def init_tree(tree: Any, key: jax.Array, dtype=None) -> Any:
    """Materialize a decl tree with per-leaf independent keys.

    Each leaf is drawn in its own compiled program on the default device;
    ``dtype`` (e.g. bfloat16) casts every floating leaf inside that program,
    so a leaf's float32 draw never outlives it and no float32 copy of the
    whole tree ever exists (a 2.5 B-parameter model would need 10 GB for
    one). The values are bit-identical to casting the float32 tree.
    """
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_decl)
    keys = jax.random.split(key, max(len(leaves), 1))
    dtype = None if dtype is None else jnp.dtype(dtype)
    return jax.tree.unflatten(
        treedef, [_materialize(d, k, dtype) for d, k in zip(leaves, keys)])


@functools.partial(jax.jit, static_argnums=(0, 2))
def _materialize(decl: ParamDecl, key: jax.Array, dtype) -> jax.Array:
    out = decl.materialize(key)
    if dtype is None or not jnp.issubdtype(out.dtype, jnp.floating):
        return out
    return out.astype(dtype)


def count_params(tree: Any) -> int:
    leaves = jax.tree.leaves(tree, is_leaf=is_decl)
    total = 0
    for leaf in leaves:
        shape = leaf.shape if hasattr(leaf, "shape") else ()
        total += int(np.prod(shape)) if shape else 1
    return total

