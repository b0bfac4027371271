"""Granite hybrid: Mamba-2 layers with a GQA layer every few, a SwiGLU MLP
after every layer, granite's four multipliers (HF ``granitemoehybrid``
with no experts; granite-4.0-h-micro).

Each layer is ``x + r·mixer(norm(x))`` then ``x + r·mlp(norm(x))``, where
``r`` is ``residual_multiplier`` and the mixer is the kind
``layer_types`` names:

* ``mamba`` — in_proj to z, x, B, C and dt; a causal depthwise conv of
  width ``mamba_d_conv`` with bias over x, B and C, **then** SiLU;
  ``dt = softplus(dt + dt_bias)``; per head ``h ← h·exp(dt·A) +
  (dt·x) ⊗ B`` and ``y = h·C + D·x`` with ``A = -exp(A_log)``; a gated
  RMSNorm of ``y·silu(z)`` over d_inner; out_proj;
* ``attention`` — grouped-query attention with no positional encoding
  (``position_embedding_type`` ``nope``), scores scaled by
  ``attention_multiplier``.

Embeddings are multiplied by ``embedding_multiplier`` and logits divided
by ``logits_scaling``. The equations of ``granite-4.0-h-micro`` as the
program serves them, for every part of the benchmark that depends on
them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference import F32, HI, _mm, _rmsnorm
from bench.spec import head_dim

# equations the program has, as a configuration file states them
_FIXED = {"hidden_act": "silu", "normalization_function": "rmsnorm",
          "position_embedding_type": "nope", "mamba_n_groups": 1,
          "mamba_conv_bias": True, "mamba_proj_bias": False,
          "attention_bias": False, "num_local_experts": 0}


def _period(cfg: dict) -> list[str]:
    kinds = list(cfg["layer_types"])
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return kinds[:p]
    raise ValueError(f"{cfg['name']}: empty layer_types")


def _mamba_dims(cfg: dict) -> tuple[int, int, int, int]:
    """(heads, head width, state, d_inner) of a Mamba-2 layer."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    di = cfg["mamba_expand"] * cfg["hidden_size"]
    if h * p != di:
        raise ValueError(f"{cfg['name']}: {h} heads of {p} is not d_inner {di}")
    return h, p, cfg["mamba_d_state"], di


def program_config(cfg: dict) -> dict:
    """Keyword arguments of the program's ``ModelConfig``."""
    for key, want in _FIXED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{cfg['name']}: {key}={cfg[key]!r}; the "
                             f"program serves only {want!r}")
    _mamba_dims(cfg)
    return dict(
        name=cfg["name"], family="ssm_hybrid",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        d_ff=cfg["shared_intermediate_size"], vocab=cfg["vocab_size"],
        mlp_type="swiglu", pos_emb="none",
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"], layer_types=tuple(cfg["layer_types"]),
        ssm_state=cfg["mamba_d_state"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_expand=cfg["mamba_expand"], ssm_conv_width=cfg["mamba_d_conv"],
        ssm_chunk=cfg["mamba_chunk_size"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]))


def shapes(cfg: dict) -> dict:
    """``{path: (shape, fan_in or init tag)}``, stacked by period: layer
    ``j`` of the period under ``blocks["l{j}"]``, its mixer under
    ``mamba`` or ``attn``. ``A = -exp(A_log)`` spreads around -1 and
    ``dt_bias`` around 0, so a state keeps a few tokens; D is near 1.

    The embedding is drawn at 0.02 over ``embedding_multiplier``, so the
    scaled input has the 0.02 of the dense models. Drawn at 0.02 and
    multiplied by 12, the input token's own embedding stays in the last
    hidden state strongly enough that the tied head ranks that token first
    at every position, far ahead of the rest (at the published widths and
    depth): the model repeats its input, and no rounding of the forward,
    float8 included, changes a served token."""
    kinds = _period(cfg)
    n = cfg["num_hidden_layers"] // len(kinds)
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["shared_intermediate_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    mh, _, ns, di = _mamba_dims(cfg)
    w = cfg["mamba_d_conv"]
    out = {"embed": ((v, d), ("normal", 0.02 / cfg["embedding_multiplier"])),
           "ln_f": ((d,), "norm")}
    for j, kind in enumerate(kinds):
        lp = f"blocks/l{j}"
        if kind == "attention":
            out.update({
                f"{lp}/attn/ln": ((n, d), "norm"),
                f"{lp}/attn/wq": ((n, d, h, hd), d),
                f"{lp}/attn/wk": ((n, d, kv, hd), d),
                f"{lp}/attn/wv": ((n, d, kv, hd), d),
                f"{lp}/attn/wo": ((n, h, hd, d), h * hd)})
        else:
            m = f"{lp}/mamba"
            out.update({
                f"{m}/ln": ((n, d), "norm"),
                f"{m}/w_z": ((n, d, di), d), f"{m}/w_x": ((n, d, di), d),
                f"{m}/w_B": ((n, d, ns), d), f"{m}/w_C": ((n, d, ns), d),
                f"{m}/w_dt": ((n, d, mh), d),
                f"{m}/conv_x": ((n, w, di), w),
                f"{m}/conv_x_b": ((n, di), ("normal", 0.5)),
                f"{m}/conv_B": ((n, w, ns), w),
                f"{m}/conv_B_b": ((n, ns), ("normal", 0.5)),
                f"{m}/conv_C": ((n, w, ns), w),
                f"{m}/conv_C_b": ((n, ns), ("normal", 0.5)),
                f"{m}/A_log": ((n, mh), ("normal", 0.5)),
                f"{m}/D": ((n, mh), "norm"),
                f"{m}/dt_bias": ((n, mh), ("normal", 0.5)),
                f"{m}/ln_gate": ((n, di), "norm"),
                f"{m}/w_out": ((n, di, d), di)})
        out.update({
            f"{lp}/ln_mlp": ((n, d), "norm"),
            f"{lp}/mlp/w_gate": ((n, d, f), d),
            f"{lp}/mlp/w_up": ((n, d, f), d),
            f"{lp}/mlp/w_down": ((n, f, d), f)})
    if not cfg["tie_word_embeddings"]:
        out["head"] = ((d, v), d)
    return out


def _consts(cfg: dict) -> dict:
    mh, mp, ns, di = _mamba_dims(cfg)
    return dict(
        kinds=_period(cfg), eps=cfg["rms_norm_eps"],
        groups=cfg["num_attention_heads"] // cfg["num_key_value_heads"],
        heads=mh, head=mp, state=ns,
        emb_mult=float(cfg["embedding_multiplier"]),
        attn_mult=float(cfg["attention_multiplier"]),
        res_mult=float(cfg["residual_multiplier"]),
        logit_div=float(cfg["logits_scaling"]),
        vocab=cfg["vocab_size"], tied=cfg["tie_word_embeddings"])


def _attention(c, quant, x, a):
    s = x.shape[0]
    h = _rmsnorm(x, a["ln"], c["eps"])
    q = _mm("sd,dhk->shk", h, a["wq"], quant, (0,))
    k = jnp.repeat(_mm("sd,dhk->shk", h, a["wk"], quant, (0,)), c["groups"], 1)
    v = jnp.repeat(_mm("sd,dhk->shk", h, a["wv"], quant, (0,)), c["groups"], 1)
    scores = jnp.einsum("qhk,chk->hqc", q, k, precision=HI) * c["attn_mult"]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqc,chk->qhk", p, v, precision=HI)
    return _mm("shk,hkd->sd", o, a["wo"], quant, (0, 1))


def _conv_silu(v, w, b):
    """Causal depthwise conv over time (S, C) with bias, then SiLU."""
    width = w.shape[0]
    pad = jnp.pad(v, ((width - 1, 0), (0, 0)))
    y = sum(pad[i:i + v.shape[0]] * w[i] for i in range(width))
    return jax.nn.silu(y + b)


def _written(a):
    """``a`` unchanged, through an identity scatter, which XLA fuses into
    none of its users: the recurrence's inputs and each step's state are
    computed once. Without it XLA fuses the whole sequence's inputs, and
    the chain of earlier steps, into every step, and its cost analysis
    counts that work once a step."""
    return jnp.zeros_like(a).at[jnp.arange(a.shape[0])].set(a)


def _mamba(c, quant, x, m):
    s = x.shape[0]
    h = _rmsnorm(x, m["ln"], c["eps"])
    z = _mm("sd,de->se", h, m["w_z"], quant, (0,))
    xs = _conv_silu(_mm("sd,de->se", h, m["w_x"], quant, (0,)),
                    m["conv_x"], m["conv_x_b"])
    B = _conv_silu(_mm("sd,dn->sn", h, m["w_B"], quant, (0,)),
                   m["conv_B"], m["conv_B_b"])
    C = _conv_silu(_mm("sd,dn->sn", h, m["w_C"], quant, (0,)),
                   m["conv_C"], m["conv_C_b"])
    dt = jax.nn.softplus(_mm("sd,dh->sh", h, m["w_dt"], quant, (0,))
                         + m["dt_bias"])
    xh = xs.reshape(s, c["heads"], c["head"])
    decay = jnp.exp(dt * -jnp.exp(m["A_log"]))          # (S, H)
    dtx = dt[:, :, None] * xh                             # (S, H, P)

    def step(state, inp):                 # one token, products elementwise
        decay_t, dtx_t, B_t, C_t = inp
        state = _written(state * decay_t[:, None, None]
                         + dtx_t[:, :, None] * B_t[None, None, :])
        return state, jnp.sum(state * C_t[None, None, :], axis=-1)

    state0 = jnp.zeros((c["heads"], c["head"], c["state"]), F32)
    _, y = lax.scan(step, state0, tuple(map(_written, (decay, dtx, B, C))))
    y = (y + m["D"][:, None] * xh).reshape(s, -1)
    g = _rmsnorm(y * jax.nn.silu(z), m["ln_gate"], c["eps"])
    return _mm("se,ed->sd", g, m["w_out"], quant, (0,))


def logits(cfg: dict, params, tokens, quant: bool = False):
    """(S, vocab) float32 logits of one sequence ``tokens`` (S,): every
    layer in float32 at ``Precision.HIGHEST``, its weights upcast one
    layer at a time, each Mamba-2 layer a sequential recurrence over the
    tokens; ``quant`` feeds every matrix product float8."""
    c = _consts(cfg)
    emb = params["embed"].astype(F32)
    x = emb[tokens] * c["emb_mult"]
    r = c["res_mult"]

    def body(x, blk):
        for j, kind in enumerate(c["kinds"]):
            lp = jax.tree.map(lambda w: w.astype(F32), blk[f"l{j}"])
            if kind == "attention":
                x = x + r * _attention(c, quant, x, lp["attn"])
            else:
                x = x + r * _mamba(c, quant, x, lp["mamba"])
            mlp = lp["mlp"]
            h = _rmsnorm(x, lp["ln_mlp"], c["eps"])
            g = _mm("sd,df->sf", h, mlp["w_gate"], quant, (0,))
            u = _mm("sd,df->sf", h, mlp["w_up"], quant, (0,))
            x = x + r * _mm("sf,fd->sd", jax.nn.silu(g) * u, mlp["w_down"],
                            quant, (0,))
        return x, None

    x, _ = lax.scan(body, x, params["blocks"])
    x = _rmsnorm(x, params["ln_f"].astype(F32), c["eps"])
    if c["tied"]:
        out = _mm("sd,vd->sv", x, emb, quant, (1,))
    else:
        out = _mm("sd,dv->sv", x, params["head"].astype(F32), quant, (0,))
    return out[:, :c["vocab"]] / c["logit_div"]


def _counts(cfg: dict) -> tuple[int, int]:
    kinds = cfg["layer_types"]
    return kinds.count("attention"), kinds.count("mamba")


def matmul_weights(cfg: dict) -> int:
    """Weights a token multiplies through: in_proj and out_proj of each
    Mamba-2 layer, q, k, v and o of each attention layer, the SwiGLU of
    every layer and the output head."""
    d, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 head_dim(cfg))
    mh, _, ns, di = _mamba_dims(cfg)
    n_attn, n_mamba = _counts(cfg)
    mamba = d * (2 * di + 2 * ns + mh) + di * d
    attn = d * h * hd * 2 + d * kv * hd * 2
    return (n_attn * attn + n_mamba * mamba
            + cfg["num_hidden_layers"] * 3 * d * f + d * cfg["vocab_size"])


def tokens_flops(cfg: dict, tokens: int, attended: int) -> float:
    """FLOPs of ``tokens`` fed tokens that attend over ``attended``
    positions in all: two a weight, ``4 · heads · head_dim`` a position
    in each attention layer, and the recurrence's ``5 · H · P · N`` a
    token in each Mamba-2 layer (decay, injection and its add, and the
    readout's product and sum)."""
    h, hd = cfg["num_attention_heads"], head_dim(cfg)
    n_attn, n_mamba = _counts(cfg)
    mh, mp, ns, _ = _mamba_dims(cfg)
    return (2.0 * matmul_weights(cfg) * tokens
            + 4.0 * n_attn * h * hd * attended
            + 5.0 * n_mamba * mh * mp * ns * tokens)


def attention_layers(cfg: dict) -> list[tuple[int, int, int]]:
    """``(heads, kv_heads, head_dim)`` of each layer that holds KV."""
    n_attn, _ = _counts(cfg)
    return [(cfg["num_attention_heads"], cfg["num_key_value_heads"],
             head_dim(cfg))] * n_attn


def ssm_layers(cfg: dict) -> list[tuple[int, int, int]]:
    """``(heads, head width, state)`` of each layer that holds recurrent
    state (``bench/kernels/ssm_decode.py``)."""
    _, n_mamba = _counts(cfg)
    mh, mp, ns, _ = _mamba_dims(cfg)
    return [(mh, mp, ns)] * n_mamba


def small(cfg: dict) -> dict:
    """``cfg`` cut to two periods of a Mamba-2 and an attention layer at
    width 128 (4 Mamba-2 heads of 64 at the published state of 128; 4
    query over 2 KV heads of 32) and 4 slots of 128 tokens: the size of
    the CPU tests."""
    return dict(cfg, num_hidden_layers=4,
                layer_types=["mamba", "attention"] * 2, hidden_size=128,
                num_attention_heads=4, num_key_value_heads=2,
                attention_multiplier=1 / 32, mamba_n_heads=4,
                intermediate_size=256, shared_intermediate_size=256,
                vocab_size=512,
                engine={"slots": 4, "max_len": 128, "page_size": 16})
