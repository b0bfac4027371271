"""Mamba-2 (state-space duality, arXiv:2405.21060) in JAX.

The SSD scan has three backends:
  * ``ref``     — sequential recurrence over time (oracle; O(S) steps);
  * ``chunked`` — the paper's chunk-parallel SSD algorithm (matmul-rich; the
                  TPU-friendly production formulation the dry-run lowers);
  * ``pallas``  — fused chunk kernel via XAIF (:mod:`repro.kernels.ssd`).

State per layer is O(heads × head_dim × state): decode cost is independent of
context length — the long_500k-eligible property.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.ssm_decode.ref import ssm_step
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.sharding import axes as lx
from repro.sharding.params import Axes, ParamDecl

F32 = jnp.float32


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------


def segsum(x: jax.Array) -> jax.Array:
    """x: (..., l) -> (..., l, l) with out[..., i, j] = sum_{k=j+1..i} x[k]
    (=-inf above the diagonal)."""
    l = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None], (*x.shape, l))  # (..., i, j) holds x[i]
    mask_strict = jnp.tril(jnp.ones((l, l), bool), -1)  # true where j < i
    xx = jnp.where(mask_strict, xx, 0.0)
    out = jnp.cumsum(xx, axis=-2)  # out[i,j] = sum_{k=j+1..i} x[k]
    mask = jnp.tril(jnp.ones((l, l), bool), 0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x, dA, B, C, *, chunk: int, init_state=None,
                compute_dtype=F32):
    """Chunk-parallel SSD. x:(b,s,h,p) pre-scaled by dt; dA:(b,s,h);
    B,C:(b,s,h,n). Returns (y:(b,s,h,p), final_state:(b,h,p,n)).

    ``compute_dtype=bfloat16`` keeps the matmul operands (x, B, C) and the
    emitted y in bf16 (fp32 accumulation via preferred_element_type) — the
    decay math and the carried state stay fp32."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    q = min(chunk, s)
    s_orig = s
    if s % q:
        # pad with identity steps: x=0, dA=0 (decay 1) leaves state untouched
        pad = q - s % q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dA = jnp.pad(dA, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        s = s + pad
    nc = s // q

    cdt = compute_dtype

    def to_chunks(a, dt):
        return jnp.moveaxis(a.astype(dt).reshape(b, nc, q, *a.shape[2:]), 1, 0)

    xc = to_chunks(x, cdt)
    dAc = to_chunks(dA, F32)
    Bc = to_chunks(B, cdt)
    Cc = to_chunks(C, cdt)
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), F32)

    # Sequential scan over chunks (mirrors the Pallas kernel): peak memory is
    # ONE chunk's (q × q) decay tile per head instead of all chunks at once.
    def step(state, inp):
        xq, dAq, Bq, Cq = inp                   # (b,q,...) one chunk
        dAq = jnp.moveaxis(dAq, -1, 1)          # (b,h,q)
        a_cs = jnp.cumsum(dAq, axis=-1)         # (b,h,q)
        Lmat = jnp.exp(segsum(dAq)).astype(cdt)  # (b,h,q,q)
        y = jnp.einsum("blhn,bshn,bhls,bshp->blhp", Cq, Bq, Lmat, xq,
                       preferred_element_type=F32)
        # incoming-state contribution
        y = y + jnp.einsum("blhn,bhpn,bhl->blhp", Cq.astype(F32), state,
                           jnp.exp(a_cs))
        # state update
        decay_states = jnp.exp(a_cs[..., -1:] - a_cs)   # (b,h,q)
        new_state = state * jnp.exp(a_cs[..., -1])[..., None, None] \
            + jnp.einsum("blhn,bhl,blhp->bhpn", Bq.astype(F32), decay_states,
                         xq.astype(F32))
        return new_state, y.astype(cdt)

    final_state, ys = lax.scan(step, init_state.astype(F32), (xc, dAc, Bc, Cc))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, h, p)[:, :s_orig]
    return y.astype(x.dtype), final_state


def ssd_ref(x, dA, B, C, *, init_state=None, chunk: int = 0):
    """Sequential oracle recurrence."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), F32)

    def step(state, inp):
        x_t, dA_t, B_t, C_t = inp
        state = state * jnp.exp(dA_t.astype(F32))[..., None, None] \
            + jnp.einsum("bhp,bhn->bhpn", x_t.astype(F32), B_t.astype(F32))
        y = jnp.einsum("bhpn,bhn->bhp", state, C_t.astype(F32))
        return state, y

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dA, 1, 0),
          jnp.moveaxis(B, 1, 0), jnp.moveaxis(C, 1, 0))
    final, ys = lax.scan(step, init_state.astype(F32), xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), final


def ssd(x, dA, B, C, *, impl: str, chunk: int, init_state=None,
        compute_dtype=F32):
    if impl == "ref":
        return ssd_ref(x, dA, B, C, init_state=init_state)
    if impl == "chunked":
        return ssd_chunked(x, dA, B, C, chunk=chunk, init_state=init_state,
                           compute_dtype=compute_dtype)
    from repro.core.xaif import REGISTRY

    return REGISTRY.dispatch("ssd", impl, x, dA, B, C, chunk=chunk,
                             init_state=init_state)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


def _layer_decls(cfg: ModelConfig) -> dict[str, Any]:
    """One Mamba-2 block: its pre-norm ``ln`` and the mixer's weights. The
    published in_proj is split into its five outputs (z, x, B, C, dt) and
    the conv over xBC into its three channel groups, each with its bias."""
    d, di, h, n, w = (cfg.d_model, cfg.d_inner, cfg.ssm_heads, cfg.ssm_state,
                      cfg.ssm_conv_width)
    return {
        "ln": L.rmsnorm_decl(d),
        "w_z": ParamDecl((d, di), Axes(lx.EMBED, lx.RNN_WIDTH), init="fan_in"),
        "w_x": ParamDecl((d, di), Axes(lx.EMBED, lx.RNN_WIDTH), init="fan_in"),
        "w_B": ParamDecl((d, n), Axes(lx.EMBED, lx.STATE), init="fan_in"),
        "w_C": ParamDecl((d, n), Axes(lx.EMBED, lx.STATE), init="fan_in"),
        "w_dt": ParamDecl((d, h), Axes(lx.EMBED, lx.HEADS), init="fan_in"),
        "conv_x": L.conv1d_decl(w, di),
        "conv_x_b": ParamDecl((di,), Axes(lx.RNN_WIDTH), init="zeros"),
        "conv_B": ParamDecl((w, n), Axes(lx.CONV, lx.STATE), init="fan_in"),
        "conv_B_b": ParamDecl((n,), Axes(lx.STATE), init="zeros"),
        "conv_C": ParamDecl((w, n), Axes(lx.CONV, lx.STATE), init="fan_in"),
        "conv_C_b": ParamDecl((n,), Axes(lx.STATE), init="zeros"),
        "A_log": ParamDecl((h,), Axes(lx.HEADS), init="zeros"),
        "D": ParamDecl((h,), Axes(lx.HEADS), init="ones"),
        "dt_bias": ParamDecl((h,), Axes(lx.HEADS), init="zeros"),
        "ln_gate": ParamDecl((di,), Axes(lx.RNN_WIDTH), init="ones"),
        "w_out": ParamDecl((di, d), Axes(lx.RNN_WIDTH, lx.EMBED), init="fan_in"),
    }


def decls(cfg: ModelConfig) -> dict[str, Any]:
    from repro.sharding.params import stack_tree

    tree: dict[str, Any] = {
        "embed": L.embed_decl(cfg),
        "blocks": stack_tree(_layer_decls(cfg), cfg.n_layers, lx.LAYERS),
        "ln_f": L.rmsnorm_decl(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        tree["head"] = L.head_decl(cfg)
    return tree


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SSMCache:
    conv_x: jax.Array   # (L, W-1, B, d_inner) last conv inputs, oldest first
    conv_B: jax.Array   # (L, W-1, B, n)
    conv_C: jax.Array   # (L, W-1, B, n)
    state: jax.Array    # (L, B, H, P, N)
    pos: jax.Array

    @staticmethod
    def _shapes(cfg: ModelConfig, batch: int, layers: int | None = None):
        w = cfg.ssm_conv_width
        nl = cfg.n_layers if layers is None else layers
        return (
            (nl, w - 1, batch, cfg.d_inner),
            (nl, w - 1, batch, cfg.ssm_state),
            (nl, w - 1, batch, cfg.ssm_state),
            (nl, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
        )

    @staticmethod
    def init(cfg, batch, max_len=None, dtype=jnp.bfloat16,
             layers: int | None = None) -> "SSMCache":
        s = SSMCache._shapes(cfg, batch, layers)
        return SSMCache(jnp.zeros(s[0], dtype), jnp.zeros(s[1], dtype),
                        jnp.zeros(s[2], dtype), jnp.zeros(s[3], jnp.float32),
                        jnp.zeros((), jnp.int32))

    @staticmethod
    def abstract(cfg, batch, max_len=None, dtype=jnp.bfloat16,
                 layers: int | None = None) -> "SSMCache":
        s = SSMCache._shapes(cfg, batch, layers)
        return SSMCache(jax.ShapeDtypeStruct(s[0], dtype),
                        jax.ShapeDtypeStruct(s[1], dtype),
                        jax.ShapeDtypeStruct(s[2], dtype),
                        jax.ShapeDtypeStruct(s[3], jnp.float32),
                        jax.ShapeDtypeStruct((), jnp.int32))

    @staticmethod
    def axes() -> "SSMCache":
        return SSMCache(
            Axes(lx.LAYERS, None, lx.DECODE_BATCH, lx.RNN_WIDTH),
            Axes(lx.LAYERS, None, lx.DECODE_BATCH, lx.STATE),
            Axes(lx.LAYERS, None, lx.DECODE_BATCH, lx.STATE),
            Axes(lx.LAYERS, lx.DECODE_BATCH, lx.HEADS, lx.HEAD_DIM, lx.STATE),
            Axes(),
        )


# ---------------------------------------------------------------------------
# The mixer (shared with the Mamba-2 layers of models/hybrid.py)
# ---------------------------------------------------------------------------


def _project(h, lp):
    """in_proj of the normed input: z, x, B, C, dt (before the conv)."""
    return tuple(h @ lp[k].astype(h.dtype)
                 for k in ("w_z", "w_x", "w_B", "w_C", "w_dt"))


def _conv(v, lp, group: str):
    """Causal depthwise conv of one channel group over a sequence (B, S, C)
    with its bias, **then** SiLU. Returns (activations, the last W-1 conv
    inputs as (W-1, B, C))."""
    y, tail = L.causal_conv1d(v, lp[f"conv_{group}"].astype(v.dtype))
    return (jax.nn.silu(y + lp[f"conv_{group}_b"].astype(y.dtype)),
            jnp.swapaxes(tail, 0, 1))


def _conv_step(v, lp, group: str, tail):
    """:func:`_conv` for one token ``v`` (B, C) after the inputs ``tail``
    (W-1, B, C), summed in the same order. Returns (activations (B, C),
    the new tail)."""
    w = lp[f"conv_{group}"].astype(v.dtype)
    xx = jnp.concatenate([tail, v[None]], axis=0)
    y = sum(xx[i] * w[i] for i in range(w.shape[0]))
    return (jax.nn.silu(y + lp[f"conv_{group}_b"].astype(y.dtype)),
            xx[1:].astype(tail.dtype))


def _dt_A(dt_raw, lp):
    dt = jax.nn.softplus(dt_raw.astype(F32) + lp["dt_bias"].astype(F32))
    return dt, -jnp.exp(lp["A_log"].astype(F32))


def _gate_out(y, z, lp, cfg: ModelConfig, dtype):
    """Gated RMSNorm of ``y·silu(z)`` over d_inner, then out_proj."""
    g = L.rmsnorm(y * jax.nn.silu(z.astype(F32)), lp["ln_gate"], cfg.norm_eps)
    g = g.astype(dtype)
    return g @ lp["w_out"].astype(dtype)


def mix_seq(h, lp, cfg: ModelConfig):
    """The mixer over a whole sequence ``h`` (B, S, D), already normed.
    Returns ``(out (B, S, D), conv tails (x, B, C), final state)``."""
    b, s = h.shape[:2]
    hn, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    z, xr, Br, Cr, dt_raw = _project(h, lp)
    x, tx = _conv(xr, lp, "x")
    Bm, tB = _conv(Br, lp, "B")
    Cm, tC = _conv(Cr, lp, "C")
    dt, A = _dt_A(dt_raw, lp)
    xh = x.reshape(b, s, hn, p)
    y, state = ssd(xh.astype(F32) * dt[..., None], dt * A,
                   jnp.broadcast_to(Bm[:, :, None, :], (b, s, hn, n)),
                   jnp.broadcast_to(Cm[:, :, None, :], (b, s, hn, n)),
                   impl=cfg.scan_impl, chunk=cfg.ssm_chunk,
                   compute_dtype=jnp.dtype(cfg.ssm_compute_dtype))
    y = y.astype(F32) + xh.astype(F32) * lp["D"].astype(F32)[:, None]
    out = _gate_out(y.reshape(b, s, cfg.d_inner), z, lp, cfg, h.dtype)
    return out, (tx, tB, tC), state


def mix_step(h, lp, cfg: ModelConfig, tails, recur):
    """The mixer on one token ``h`` (B, 1, D), already normed, against the
    conv tails ``(x, B, C)``, each (W-1, B, channels). ``recur(x, dt, A, B, C, D)`` runs
    the state-space recurrence (``kernels/ssm_decode``) on the state it
    holds and returns ``(y (B, H, P) f32, its new state)``. Returns
    ``(out (B, 1, D), new tails, recur's new state)``."""
    b = h.shape[0]
    hn, p = cfg.ssm_heads, cfg.ssm_head_dim
    with jax.named_scope("ssm.in_proj"):
        z, xr, Br, Cr, dt_raw = _project(h, lp)
    with jax.named_scope("ssm.conv"):
        x, tx = _conv_step(xr[:, 0], lp, "x", tails[0])
        Bm, tB = _conv_step(Br[:, 0], lp, "B", tails[1])
        Cm, tC = _conv_step(Cr[:, 0], lp, "C", tails[2])
        dt, A = _dt_A(dt_raw[:, 0], lp)
    y, state = recur(x.reshape(b, hn, p), dt, A, Bm, Cm, lp["D"].astype(F32))
    with jax.named_scope("ssm.out"):
        out = _gate_out(y.reshape(b, 1, cfg.d_inner), z, lp, cfg, h.dtype)
    return out, (tx, tB, tC), state


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


def forward(params, cfg: ModelConfig, tokens=None, embeds=None, positions=None):
    x = params["embed"].astype(jnp.bfloat16)[tokens] if embeds is None else embeds

    def body(carry, lp):
        out, _, _ = mix_seq(L.rmsnorm(carry, lp["ln"], cfg.norm_eps), lp, cfg)
        return carry + out, None

    from repro.models.transformer import _maybe_remat

    x, _ = lax.scan(_maybe_remat(body, cfg), x, params["blocks"])
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return L.lm_head(x, params, cfg), jnp.zeros((), F32)


def prefill(params, cfg: ModelConfig, tokens=None, embeds=None, max_len=None):
    """Prompt pass producing the SSM cache (final conv tails + states)."""
    x = params["embed"].astype(jnp.bfloat16)[tokens] if embeds is None else embeds
    s = x.shape[1]

    def body(carry, lp):
        out, tails, st = mix_seq(L.rmsnorm(carry, lp["ln"], cfg.norm_eps),
                                 lp, cfg)
        return carry + out, (tails, st)

    body_fn = body if cfg.remat == "none" else jax.checkpoint(body)
    x, (tails, states) = lax.scan(body_fn, x, params["blocks"])
    xf = L.rmsnorm(x[:, -1:], params["ln_f"], cfg.norm_eps)
    logits = L.lm_head(xf, params, cfg)[:, 0]
    cache = SSMCache(tails[0].astype(jnp.bfloat16), tails[1].astype(jnp.bfloat16),
                     tails[2].astype(jnp.bfloat16), states.astype(F32),
                     jnp.asarray(s, jnp.int32))
    return logits, cache


def decode_step(params, cfg: ModelConfig, cache: SSMCache, tokens):
    """tokens: (B,1) -> (logits (B,V), cache')."""
    x = params["embed"].astype(jnp.bfloat16)[tokens]

    def body(carry, inp):
        lp, cx, cB, cC, st = inp
        out, (cx, cB, cC), st = mix_step(
            L.rmsnorm(carry, lp["ln"], cfg.norm_eps), lp, cfg, (cx, cB, cC),
            lambda *a: ssm_step(st, *a))
        return carry + out, (cx, cB, cC, st)

    x, (cx, cB, cC, st) = lax.scan(
        body, x, (params["blocks"], cache.conv_x, cache.conv_B, cache.conv_C,
                  cache.state))
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = L.lm_head(x, params, cfg)[:, 0]
    return logits, SSMCache(cx, cB, cC, st, cache.pos + 1)
