"""Plain float32 reference of the served model, and its low-precision control.

It reads only the configuration file and the benchmark's own weights, and
imports nothing of the program. One sequence at a time, every layer in
float32 at ``Precision.HIGHEST``. The equations are the configuration's
model file's (``bench/models/<equations>.py`` ``logits``); the helpers
they share — the float8 rounding, the matrix product that takes it,
RMSNorm and rotary embedding — are here.

``served_gaps`` reads, at each position, by how much the logit of the token
that follows lies below the reference's best logit. ``control_gaps`` runs
the same forward with every matrix product fed float8 (e4m3) operands —
weights scaled per output channel, activations per token — and reads the
float32 gap of the token that the float8 model puts first: the control
that the comparison must fail.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

from bench import spec

F32 = jnp.float32
HI = lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x, axes):
    """Round ``x`` to float8 e4m3 with one absmax scale per slice over ``axes``."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(F32) * s


def _mm(subscripts, x, w, quant, w_axes):
    """einsum in float32; with ``quant`` both operands pass through fp8:
    activations by token (last axis), weights by output channel."""
    if quant:
        x = _fp8(x, (x.ndim - 1,))
        w = _fp8(w, w_axes)
    return jnp.einsum(subscripts, x, w, precision=HI)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta, rot):
    """Rotate the first ``rot`` dims of each head (halves paired)."""
    half = rot // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None, None] * freqs      # (S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def logits(cfg: dict, params, tokens, quant: bool = False):
    """(S, vocab) float32 logits of one sequence ``tokens`` (S,); with
    ``quant`` every matrix product is fed float8 operands."""
    return spec.model(cfg).logits(cfg, params, tokens, quant)


def _gap_of(lg, toks):
    return lg.max(-1) - jnp.take_along_axis(lg, toks[:, None], 1)[:, 0]


@functools.partial(jax.jit, static_argnums=0)
def _served(key, params, tokens):
    cfg = key.cfg
    lg = logits(cfg, params, tokens)
    return _gap_of(lg[:-1], tokens[1:])


@functools.partial(jax.jit, static_argnums=0)
def _control(key, params, tokens):
    cfg = key.cfg
    ref = logits(cfg, params, tokens)[:-1]
    low = logits(cfg, params, tokens, quant=True)[:-1]
    return _gap_of(ref, jnp.argmax(low, -1).astype(jnp.int32))


class _Key:
    """A configuration as a static argument of ``jax.jit``: the whole
    dict, nested groups and lists included, compared by its JSON text."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.text = json.dumps(cfg, sort_keys=True)

    def __hash__(self):
        return hash(self.text)

    def __eq__(self, other):
        return isinstance(other, _Key) and other.text == self.text


def _pad(tokens, length: int):
    return jnp.asarray(list(tokens) + [0] * (length - len(tokens)), jnp.int32)


def served_gaps(cfg: dict, params, prompt, served, length: int) -> list[float]:
    """Gap below the reference's best logit of each served token.

    The sequence ``prompt + served`` is padded to ``length`` so every call
    shares one compiled program; padding sits after every read position
    and the attention is causal, so it changes nothing read."""
    seq = list(prompt) + list(served)
    gaps = _served(_Key(cfg), params, _pad(seq, length))
    first = len(prompt) - 1
    return [float(g) for g in jax.device_get(gaps[first:first + len(served)])]


def control_gaps(cfg: dict, params, prompt, served, length: int) -> list[float]:
    """At each served position, the float32 gap of the token the float8
    forward ranks first, over the same prompt and served tokens."""
    seq = list(prompt) + list(served)
    gaps = _control(_Key(cfg), params, _pad(seq, length))
    first = len(prompt) - 1
    return [float(g) for g in jax.device_get(gaps[first:first + len(served)])]
