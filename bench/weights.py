"""Random weights for a configuration file, made on the device from a seed.

One jitted call draws the whole tree, in the served dtype, so set-up pays
one program (read back from the persistent compile cache after the first
run) and no leaf ever exists in float32 outside that program. The tree has
the layout the serving engine takes: stacked layers under
``blocks["l0"]``, projections shaped ``(in, heads, head_dim)`` and
``(heads, head_dim, out)``.

Projections are drawn N(0, 1/fan_in) over their contracted axes, the
embedding N(0, 0.02), and norm scales 1 + N(0, 0.1). With that scaling a
40-layer stack stays well conditioned, so a rounding-sized difference in
one layer stays rounding-sized at the logits (see ``PERF.md``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.spec import head_dim


def shapes(cfg: dict) -> dict:
    """``{path: (shape, fan_in or init tag)}`` for every leaf of the tree."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, f, v = head_dim(cfg), cfg["intermediate_size"], cfg["vocab_size"]
    out = {
        "embed": ((v, d), "embed"),
        "blocks/l0/attn/ln": ((L, d), "norm"),
        "blocks/l0/attn/wq": ((L, d, h, hd), d),
        "blocks/l0/attn/wk": ((L, d, kv, hd), d),
        "blocks/l0/attn/wv": ((L, d, kv, hd), d),
        "blocks/l0/attn/wo": ((L, h, hd, d), h * hd),
        "blocks/l0/ln_mlp": ((L, d), "norm"),
        "blocks/l0/mlp/w_gate": ((L, d, f), d),
        "blocks/l0/mlp/w_up": ((L, d, f), d),
        "blocks/l0/mlp/w_down": ((L, f, d), f),
        "ln_f": ((d,), "norm"),
    }
    if not cfg["tie_word_embeddings"]:
        out["head"] = ((d, v), d)
    return out


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
    else:
        w = z * (1.0 / math.sqrt(init))
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
