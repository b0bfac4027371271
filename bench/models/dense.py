"""Dense decoder: grouped-query attention with rotary positions and a
SwiGLU MLP in every layer, RMSNorm before each, a tied or separate head.

The equations of ``granite-3-2b`` and ``stablelm-3b`` as the program
serves them, for every part of the benchmark that depends on them: the
program's ``ModelConfig``, the weight tree, the float32 reference, the
closed-form FLOPs and the layers that hold KV in the paged arena.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference import F32, HI, _mm, _rmsnorm, _rope
from bench.spec import head_dim

# equations the program has, as a configuration file states them; any
# other value is a model the program cannot serve
_FIXED = {"hidden_act": "silu", "norm_type": "rmsnorm",
          "partial_rotary_factor": 1.0, "embedding_multiplier": 1.0,
          "residual_multiplier": 1.0, "logits_scaling": 1.0,
          "torch_dtype": "bfloat16"}


def _norm_eps(cfg: dict) -> float:
    return cfg.get("rms_norm_eps", cfg.get("layer_norm_eps"))


def program_config(cfg: dict) -> dict:
    """Keyword arguments of the program's ``ModelConfig``."""
    for key, want in _FIXED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{cfg['name']}: {key}={cfg[key]!r}; the "
                             f"program serves only {want!r}")
    hd = head_dim(cfg)
    if abs(cfg.get("attention_multiplier", hd ** -0.5) - hd ** -0.5) > 1e-12:
        raise ValueError(f"{cfg['name']}: the program scales attention "
                         "scores by 1/sqrt(head_dim) only")
    return dict(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=hd,
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        mlp_type="swiglu", pos_emb="rope", rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"], norm_eps=_norm_eps(cfg))


def shapes(cfg: dict) -> dict:
    """``{path: (shape, fan_in or init tag)}``: stacked layers under
    ``blocks["l0"]``, projections ``(in, heads, head_dim)`` and
    ``(heads, head_dim, out)``."""
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


def _consts(cfg: dict) -> dict:
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"reference implements silu only: {cfg['hidden_act']}")
    if cfg.get("norm_type", "rmsnorm") != "rmsnorm":
        raise ValueError(f"reference implements rmsnorm only: {cfg['norm_type']}")
    if cfg.get("attention_bias") or cfg.get("mlp_bias") or cfg.get("use_qkv_bias"):
        raise ValueError("reference has no biases")
    hd = head_dim(cfg)
    rot = int(hd * cfg.get("partial_rotary_factor", 1.0))
    return dict(
        hd=hd, rot=rot - rot % 2,
        groups=cfg["num_attention_heads"] // cfg["num_key_value_heads"],
        eps=_norm_eps(cfg),
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
    """(S, vocab) float32 logits of one sequence ``tokens`` (S,): every
    layer in float32 at ``Precision.HIGHEST``, with the multipliers the
    configuration states; ``quant`` feeds every matrix product float8."""
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


def matmul_weights(cfg: dict) -> int:
    """Weights a token multiplies through: the q, k, v and o projections
    and the three SwiGLU matrices of every layer, and the output head."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 head_dim(cfg))
    per_layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * cfg["intermediate_size"]
    return L * per_layer + d * cfg["vocab_size"]


def tokens_flops(cfg: dict, tokens: int, attended: int) -> float:
    """FLOPs of ``tokens`` fed tokens that attend over ``attended``
    positions in all: two a weight, and ``4 · heads · head_dim`` a
    position in every layer for the scores and the weighted sum."""
    L, h, hd = cfg["num_hidden_layers"], cfg["num_attention_heads"], head_dim(cfg)
    return 2.0 * matmul_weights(cfg) * tokens + 4.0 * L * h * hd * attended


def attention_layers(cfg: dict) -> list[tuple[int, int, int]]:
    """``(heads, kv_heads, head_dim)`` of each layer: every layer attends."""
    return [(cfg["num_attention_heads"], cfg["num_key_value_heads"],
             head_dim(cfg))] * cfg["num_hidden_layers"]


def small(cfg: dict) -> dict:
    """``cfg`` cut to 4 layers of width 128 and 4 slots of 128 tokens: the
    size of the CPU tests and of the recorded chip trace. Grouped heads
    stay grouped (4 over 2 KV heads) and MHA stays MHA."""
    mha = cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    return dict(cfg, num_hidden_layers=4, hidden_size=128,
                num_attention_heads=4, num_key_value_heads=4 if mha else 2,
                intermediate_size=256, vocab_size=512,
                attention_multiplier=32 ** -0.5,
                engine={"slots": 4, "max_len": 128, "page_size": 16})
