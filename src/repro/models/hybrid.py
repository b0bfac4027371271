"""Mamba-2 / attention hybrid (granite-4.0-h, Nemotron-H) in JAX.

Each layer is one mixer — a Mamba-2 mixer or grouped-query attention with
no positional encoding — then a SwiGLU MLP, each behind an RMSNorm and a
residual scaled by ``residual_multiplier`` (HF
``GraniteMoeHybridDecoderLayer``)::

    x ← x + r·mixer(norm(x))        x ← x + r·mlp(norm(x))

``layer_types`` repeats a period (granite-4.0-h-micro: ``mmmmmammmm``
four times), so the weights stack by period and every pass scans over
periods with one period's layers unrolled. The Mamba-2 mixer is
:mod:`repro.models.mamba2`'s; the attention layer, MLP, embedding and head
are :mod:`repro.models.transformer`'s, with granite's multipliers.

Serving state:

* the lane fallback (:func:`decode_step`) keeps a :class:`HybridCache` —
  a KV cache over the attention layers and an ``SSMCache`` over the
  Mamba-2 layers;
* the paged step (:func:`decode_step_paged`) reads a KV arena that holds
  the attention layers only (``transformer.paged_pool_init`` sized by
  :func:`n_attention`) and carries a per-slot **state arena** beside it,
  an ``SSMCache`` with a row per slot (:func:`state_init`): conv tails in
  the activations' dtype and the recurrent state in float32. Both arenas
  ride the layer scan in place; the recurrence is the ``ssm_decode``
  kernel, which reads and writes a layer's state rows where they lie.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.ssm_decode import ops as ssm_ops
from repro.kernels.ssm_decode.ref import ssm_step
from repro.models import layers as L
from repro.models import mamba2, transformer
from repro.models.config import ModelConfig
from repro.models.mamba2 import SSMCache
from repro.sharding import axes as lx
from repro.sharding.params import stack_tree


def period(cfg: ModelConfig) -> tuple[str, ...]:
    """The shortest run of ``layer_types`` whose repeats make the stack."""
    kinds = tuple(cfg.layer_types)
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return kinds[:p]
    raise ValueError(f"{cfg.name}: empty layer_types")


def n_attention(cfg: ModelConfig) -> int:
    """Layers that hold KV."""
    return cfg.layer_types.count("attention")


def n_mamba(cfg: ModelConfig) -> int:
    """Layers that hold recurrent state."""
    return cfg.layer_types.count("mamba")


def _layer_decls(cfg: ModelConfig, kind: str) -> dict[str, Any]:
    mixer = ({"attn": transformer._attn_decls(cfg)} if kind == "attention"
             else {"mamba": mamba2._layer_decls(cfg)})
    return dict(mixer, ln_mlp=L.rmsnorm_decl(cfg.d_model),
                mlp=L.mlp_decls(cfg.d_model, cfg.d_ff, cfg.mlp_type))


def decls(cfg: ModelConfig) -> dict[str, Any]:
    kinds = period(cfg)
    tree: dict[str, Any] = {
        "embed": L.embed_decl(cfg),
        "blocks": stack_tree({f"l{j}": _layer_decls(cfg, k)
                              for j, k in enumerate(kinds)},
                             cfg.n_layers // len(kinds), lx.LAYERS),
        "ln_f": L.rmsnorm_decl(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        tree["head"] = L.head_decl(cfg)
    return tree


def _mamba_layer(x, mp, cfg: ModelConfig, tails, recur):
    """A Mamba-2 mixer on one token, with its residual."""
    out, tails, state = mamba2.mix_step(
        L.rmsnorm(x, mp["ln"], cfg.norm_eps), mp, cfg, tails, recur)
    return transformer._residual(x, out, cfg), tails, state


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def forward(params, cfg: ModelConfig, tokens=None, embeds=None,
            positions=None):
    """Training forward. Returns (logits, 0)."""
    s = tokens.shape[1] if tokens is not None else embeds.shape[1]
    if positions is None:
        positions = jnp.arange(s)[None, :]
    x = transformer._embed(params, cfg, tokens, embeds, positions)
    kinds = period(cfg)

    def body(x, blk):
        for j, kind in enumerate(kinds):
            lp = blk[f"l{j}"]
            if kind == "attention":
                x = transformer._attn_train(x, _cast(lp["attn"], x.dtype),
                                            cfg, positions)
            else:
                mp = lp["mamba"]
                out, _, _ = mamba2.mix_seq(
                    L.rmsnorm(x, mp["ln"], cfg.norm_eps), mp, cfg)
                x = transformer._residual(x, out, cfg)
            x, _ = transformer._ffn(x, lp, cfg, False)
        return x, None

    x, _ = lax.scan(transformer._maybe_remat(body, cfg), x, params["blocks"])
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return transformer._head(params, cfg, x), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Lane fallback: one cache per lane
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HybridCache:
    kv: transformer.KVCache     # the attention layers'
    ssm: SSMCache               # the Mamba-2 layers'

    @staticmethod
    def init(cfg: ModelConfig, batch: int, max_len: int) -> "HybridCache":
        return HybridCache(
            transformer.KVCache.init(cfg, batch, max_len,
                                     layers=n_attention(cfg)),
            SSMCache.init(cfg, batch, layers=n_mamba(cfg)))

    @staticmethod
    def abstract(cfg: ModelConfig, batch: int, max_len: int) -> "HybridCache":
        return HybridCache(
            transformer.KVCache.abstract(cfg, batch, max_len,
                                         layers=n_attention(cfg)),
            SSMCache.abstract(cfg, batch, layers=n_mamba(cfg)))

    @staticmethod
    def axes() -> "HybridCache":
        return HybridCache(transformer.KVCache.axes(), SSMCache.axes())


def decode_step(params, cfg: ModelConfig, cache: HybridCache, tokens):
    """One token a lane against a :class:`HybridCache`: tokens (B, 1) ->
    (logits (B, V), cache')."""
    pos = cache.kv.pos
    x = transformer._embed(params, cfg, tokens, None, pos[None, None])
    kinds = period(cfg)
    n_p = cfg.n_layers // len(kinds)
    kv, ssm = cache.kv, cache.ssm

    def per_period(a):
        return a.reshape(n_p, a.shape[0] // n_p, *a.shape[1:])

    def body(x, inp):
        blk, ck, cv, cx, cB, cC, st = inp
        a = m = 0
        outs = {"k": [], "v": [], "x": [], "B": [], "C": [], "st": []}
        for j, kind in enumerate(kinds):
            lp = blk[f"l{j}"]
            if kind == "attention":
                x, k_a, v_a = transformer._attn_decode(
                    x, _cast(lp["attn"], x.dtype), cfg, ck[a], cv[a], pos)
                outs["k"].append(k_a)
                outs["v"].append(v_a)
                a += 1
            else:
                st_m = st[m]
                x, tails, st_m = _mamba_layer(
                    x, lp["mamba"], cfg, (cx[m], cB[m], cC[m]),
                    lambda *args: ssm_step(st_m, *args))
                for name, t in zip("xBC", tails):
                    outs[name].append(t)
                outs["st"].append(st_m)
                m += 1
            x, _ = transformer._ffn(x, lp, cfg, False)
        return x, tuple(jnp.stack(outs[k])
                        for k in ("k", "v", "x", "B", "C", "st"))

    x, (k, v, cx, cB, cC, st) = lax.scan(
        body, x, (params["blocks"],) + tuple(
            per_period(a) for a in (kv.k, kv.v, ssm.conv_x, ssm.conv_B,
                                    ssm.conv_C, ssm.state)))
    flat = lambda a: a.reshape(-1, *a.shape[2:])  # noqa: E731
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = transformer._head(params, cfg, x)[:, 0]
    return logits, HybridCache(
        transformer.KVCache(flat(k), flat(v), pos + 1),
        SSMCache(flat(cx), flat(cB), flat(cC), flat(st), ssm.pos + 1))


def prefill(params, cfg: ModelConfig, tokens=None, embeds=None,
            max_len: int | None = None):
    """The prompt fed token by token through :func:`decode_step` (the
    recurrence is sequential anyway). Returns (last logits, cache)."""
    if tokens is None:
        raise ValueError(f"{cfg.name}: prefill takes token ids")
    b, s = tokens.shape
    cache = HybridCache.init(cfg, b, max_len or s)

    def body(cache, tok):
        logits, cache = decode_step(params, cfg, cache, tok[:, None])
        return cache, logits

    cache, logits = lax.scan(body, cache, tokens.T)
    return logits[-1], cache


# ---------------------------------------------------------------------------
# Paged serving: a KV arena over the attention layers, a state arena beside
# ---------------------------------------------------------------------------


def state_init(cfg: ModelConfig, slots: int) -> SSMCache:
    """Zeroed state arena of ``slots`` lanes: conv tails (Lm, W-1, B, C)
    and the recurrent state (Lm, B, H, P, N) in float32."""
    return SSMCache.init(cfg, slots, layers=n_mamba(cfg))


def state_reset(state: SSMCache, slot) -> SSMCache:
    """``state`` with lane ``slot`` zeroed in every layer (a new request's
    recurrence starts from zero); donate it to zero the rows in place."""
    return SSMCache(
        *(t.at[:, :, slot].set(0) for t in (state.conv_x, state.conv_B,
                                             state.conv_C)),
        state.state.at[:, slot].set(0), state.pos)


def decode_step_paged(params, cfg: ModelConfig, pool_k, pool_v,
                      state: SSMCache, tables, lengths, tokens,
                      append_mask=None, impl: str | None = None):
    """One serving step of every lane against the two arenas.

    ``pool_k``/``pool_v`` are the flat KV arena ``(La·P, ps, Kh·Dh)`` of
    the ``La`` attention layers (attention layer ``a`` reads tables offset
    by ``a·P``); ``state`` is the arena of :func:`state_init`, whose rows
    for Mamba-2 layer ``m`` the ``ssm_decode`` kernel updates in place.
    tokens, lengths (B,); tables (B, NP); ``append_mask`` (B,) False for a
    lane riding the batch idle: its KV append is dropped and its conv
    tails and state are left bitwise as they were. ``impl`` (None) follows
    the platform, as in ``transformer.decode_step_paged``. Returns
    ``(logits (B, V), pool_k', pool_v', state')`` — donate the arenas.
    """
    from repro.kernels import backend

    if impl is None:
        impl = "pallas" if backend.on_tpu() else "ref"
    mask = (jnp.ones(tokens.shape, bool) if append_mask is None
            else append_mask)
    kinds = period(cfg)
    na, nm = kinds.count("attention"), kinds.count("mamba")
    rows = pool_k.shape[0] // n_attention(cfg)
    x = transformer._embed(params, cfg, tokens[:, None], None,
                           lengths[:, None])

    def body(carry, blk_and_period):
        x, pk, pv, st = carry
        blk, p = blk_and_period
        a = m = 0
        for j, kind in enumerate(kinds):
            lp = blk[f"l{j}"]
            if kind == "attention":
                x, pk, pv = transformer.paged_attention_layer(
                    x, lp["attn"], cfg, pk, pv, tables + (p * na + a) * rows,
                    lengths, append_mask=mask, impl=impl)
                a += 1
            else:
                layer = p * nm + m
                old = tuple(lax.dynamic_index_in_dim(t, layer, 0, False)
                            for t in (st.conv_x, st.conv_B, st.conv_C))
                x, new, ssm = _mamba_layer(
                    x, lp["mamba"], cfg, old,
                    lambda *args: ssm_ops.ssm_decode(
                        st.state, layer, *args, mask=mask, impl=impl))
                new = tuple(jnp.where(mask[None, :, None], n, o)
                            for n, o in zip(new, old))
                st = SSMCache(*(lax.dynamic_update_index_in_dim(t, n, layer, 0)
                                for t, n in zip((st.conv_x, st.conv_B,
                                                 st.conv_C), new)),
                              ssm, st.pos)
                m += 1
            with jax.named_scope("mlp"):
                x, _ = transformer._ffn(x, lp, cfg, False)
        return (x, pk, pv, st), None

    n_p = cfg.n_layers // len(kinds)
    with jax.named_scope("layers"):
        (x, pool_k, pool_v, state), _ = lax.scan(
            body, (x, pool_k, pool_v, state),
            (params["blocks"], jnp.arange(n_p, dtype=jnp.int32)))
    with jax.named_scope("head"):
        x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = transformer._head(params, cfg, x)[:, 0]
    return logits, pool_k, pool_v, state
