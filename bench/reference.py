"""Plain float32 reference of the served model, and its low-precision control.

It reads only the configuration file and the benchmark's own weights, and
imports nothing of the program. One sequence at a time, every layer in
float32 at ``Precision.HIGHEST``: RMSNorm, rotary embedding, causal
grouped-query attention, SwiGLU, a tied or separate output head, with the
multipliers the configuration states.

``served_gaps`` reads, at each position, by how much the logit of the token
that follows lies below the reference's best logit. ``control_gaps`` runs
the same forward with every matrix product fed float8 (e4m3) operands —
weights scaled per output channel, activations per token — and reads the
float32 gap of the token that the float8 model puts first: the control
that the comparison must fail.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HI = lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x, axes):
    """Round ``x`` to float8 e4m3 with one absmax scale per slice over ``axes``."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(F32) * s


def _mm(spec, x, w, quant, w_axes):
    """einsum in float32; with ``quant`` both operands pass through fp8:
    activations by token (last axis), weights by output channel."""
    if quant:
        x = _fp8(x, (x.ndim - 1,))
        w = _fp8(w, w_axes)
    return jnp.einsum(spec, x, w, precision=HI)


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


def _consts(cfg: dict) -> dict:
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"reference implements silu only: {cfg['hidden_act']}")
    if cfg.get("norm_type", "rmsnorm") != "rmsnorm":
        raise ValueError(f"reference implements rmsnorm only: {cfg['norm_type']}")
    if cfg.get("attention_bias") or cfg.get("mlp_bias") or cfg.get("use_qkv_bias"):
        raise ValueError("reference has no biases")
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    rot = int(hd * cfg.get("partial_rotary_factor", 1.0))
    return dict(
        hd=hd, rot=rot - rot % 2,
        groups=cfg["num_attention_heads"] // cfg["num_key_value_heads"],
        eps=cfg.get("rms_norm_eps", cfg.get("layer_norm_eps")),
        theta=float(cfg["rope_theta"]),
        emb_mult=float(cfg.get("embedding_multiplier", 1.0)),
        attn_mult=float(cfg.get("attention_multiplier", 1.0 / math.sqrt(hd))),
        res_mult=float(cfg.get("residual_multiplier", 1.0)),
        logit_div=float(cfg.get("logits_scaling", 1.0)),
        vocab=cfg["vocab_size"], tied=cfg["tie_word_embeddings"])


def _layer(c, quant, x, lp, pos):
    s = x.shape[0]
    a = jax.tree.map(lambda w: w.astype(F32), lp["attn"])
    h = _rmsnorm(x, a["ln"], c["eps"])
    q = _mm("sd,dhk->shk", h, a["wq"], quant, (0,))
    k = _mm("sd,dhk->shk", h, a["wk"], quant, (0,))
    v = _mm("sd,dhk->shk", h, a["wv"], quant, (0,))
    q = _rope(q, pos, c["theta"], c["rot"])
    k = _rope(k, pos, c["theta"], c["rot"])
    k = jnp.repeat(k, c["groups"], axis=1)
    v = jnp.repeat(v, c["groups"], axis=1)
    scores = jnp.einsum("qhk,chk->hqc", q, k, precision=HI) * c["attn_mult"]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqc,chk->qhk", p, v, precision=HI)
    x = x + c["res_mult"] * _mm("shk,hkd->sd", o, a["wo"], quant, (0, 1))
    m = jax.tree.map(lambda w: w.astype(F32), lp["mlp"])
    h = _rmsnorm(x, lp["ln_mlp"].astype(F32), c["eps"])
    g = _mm("sd,df->sf", h, m["w_gate"], quant, (0,))
    u = _mm("sd,df->sf", h, m["w_up"], quant, (0,))
    y = _mm("sf,fd->sd", jax.nn.silu(g) * u, m["w_down"], quant, (0,))
    return x + c["res_mult"] * y


def logits(cfg: dict, params, tokens, quant: bool = False):
    """(S, vocab) float32 logits of one sequence ``tokens`` (S,)."""
    c = _consts(cfg)
    emb = params["embed"].astype(F32)
    x = emb[tokens] * c["emb_mult"]
    pos = jnp.arange(tokens.shape[0])
    blocks = params["blocks"]["l0"]

    def body(x, lp):
        return _layer(c, quant, x, lp, pos), None

    x, _ = lax.scan(body, x, blocks)
    x = _rmsnorm(x, params["ln_f"].astype(F32), c["eps"])
    if c["tied"]:
        out = _mm("sd,vd->sv", x, emb, quant, (1,))
    else:
        out = _mm("sd,dv->sv", x, params["head"].astype(F32), quant, (0,))
    return out[:, :c["vocab"]] / c["logit_div"]


def _gap_of(lg, toks):
    return lg.max(-1) - jnp.take_along_axis(lg, toks[:, None], 1)[:, 0]


@functools.partial(jax.jit, static_argnums=0)
def _served(cfg_key, params, tokens):
    cfg = dict(cfg_key)
    lg = logits(cfg, params, tokens)
    return _gap_of(lg[:-1], tokens[1:])


@functools.partial(jax.jit, static_argnums=0)
def _control(cfg_key, params, tokens):
    cfg = dict(cfg_key)
    ref = logits(cfg, params, tokens)[:-1]
    low = logits(cfg, params, tokens, quant=True)[:-1]
    return _gap_of(ref, jnp.argmax(low, -1).astype(jnp.int32))


def _key(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, type(None)))))


def _pad(tokens, length: int):
    return jnp.asarray(list(tokens) + [0] * (length - len(tokens)), jnp.int32)


def served_gaps(cfg: dict, params, prompt, served, length: int) -> list[float]:
    """Gap below the reference's best logit of each served token.

    The sequence ``prompt + served`` is padded to ``length`` so every call
    shares one compiled program; padding sits after every read position
    and the attention is causal, so it changes nothing read."""
    seq = list(prompt) + list(served)
    gaps = _served(_key(cfg), params, _pad(seq, length))
    first = len(prompt) - 1
    return [float(g) for g in jax.device_get(gaps[first:first + len(served)])]


def control_gaps(cfg: dict, params, prompt, served, length: int) -> list[float]:
    """At each served position, the float32 gap of the token the float8
    forward ranks first, over the same prompt and served tokens."""
    seq = list(prompt) + list(served)
    gaps = _control(_key(cfg), params, _pad(seq, length))
    first = len(prompt) - 1
    return [float(g) for g in jax.device_get(gaps[first:first + len(served)])]
