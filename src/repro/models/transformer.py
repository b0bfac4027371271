"""Decoder-only transformer family: dense / GQA / SWA / MoE / modality stubs.

Structure: weights for all layers are stacked and the layer stack runs under
``lax.scan`` (bounded HLO size, fast lowering at 80 layers) with configurable
remat. MoE interleaving is expressed as a "superblock" of ``moe_interleave``
layers (dense ... dense, MoE) so the scan stays homogeneous.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels.paged_attention import ops as paged_ops
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.sharding import axes as lx
from repro.sharding.params import Axes, ParamDecl


def _is_moe_layer(cfg: ModelConfig, layer_idx: int) -> bool:
    if cfg.moe_experts < 2:
        return False
    return layer_idx % cfg.moe_interleave == cfg.moe_interleave - 1


def _attn_decls(cfg: ModelConfig) -> dict[str, ParamDecl]:
    d, h, k, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "ln": L.rmsnorm_decl(d),
        "wq": ParamDecl((d, h, hd), Axes(lx.EMBED, lx.HEADS, lx.HEAD_DIM), init="fan_in"),
        "wk": ParamDecl((d, k, hd), Axes(lx.EMBED, lx.KV_HEADS, lx.HEAD_DIM), init="fan_in"),
        "wv": ParamDecl((d, k, hd), Axes(lx.EMBED, lx.KV_HEADS, lx.HEAD_DIM), init="fan_in"),
        "wo": ParamDecl((h, hd, d), Axes(lx.HEADS, lx.HEAD_DIM, lx.EMBED), init="fan_in"),
    }


def _layer_decls(cfg: ModelConfig, layer_idx: int) -> dict[str, Any]:
    out: dict[str, Any] = {"attn": _attn_decls(cfg), "ln_mlp": L.rmsnorm_decl(cfg.d_model)}
    if _is_moe_layer(cfg, layer_idx):
        out["moe"] = L.moe_decls(cfg.d_model, cfg.d_ff, cfg.moe_experts,
                                 cfg.mlp_type, shared=cfg.moe_shared_expert)
    else:
        out["mlp"] = L.mlp_decls(cfg.d_model, cfg.d_ff, cfg.mlp_type)
    return out


def decls(cfg: ModelConfig) -> dict[str, Any]:
    il = cfg.moe_interleave if cfg.moe_experts >= 2 else 1
    if cfg.n_layers % il:
        raise ValueError(f"{cfg.name}: n_layers {cfg.n_layers} % interleave {il} != 0")
    n_super = cfg.n_layers // il
    superblock = {f"l{j}": _layer_decls(cfg, j) for j in range(il)}
    from repro.sharding.params import stack_tree

    tree: dict[str, Any] = {
        "embed": L.embed_decl(cfg),
        "blocks": stack_tree(superblock, n_super, lx.LAYERS),
        "ln_f": L.rmsnorm_decl(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        tree["head"] = L.head_decl(cfg)
    return tree


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    k: jax.Array  # (L, B, S_cache, Kh, Dh)
    v: jax.Array
    pos: jax.Array  # scalar int32 — next position to write

    @staticmethod
    def cache_len(cfg: ModelConfig, max_len: int) -> int:
        return min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len

    @staticmethod
    def _shape(cfg: ModelConfig, batch: int, max_len: int,
               layers: int | None):
        return (cfg.n_layers if layers is None else layers, batch,
                KVCache.cache_len(cfg, max_len), cfg.n_kv_heads,
                cfg.resolved_head_dim)

    @staticmethod
    def init(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
             layers: int | None = None) -> "KVCache":
        shape = KVCache._shape(cfg, batch, max_len, layers)
        return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                       jnp.zeros((), jnp.int32))

    @staticmethod
    def abstract(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
                 layers: int | None = None) -> "KVCache":
        shape = KVCache._shape(cfg, batch, max_len, layers)
        return KVCache(jax.ShapeDtypeStruct(shape, dtype),
                       jax.ShapeDtypeStruct(shape, dtype),
                       jax.ShapeDtypeStruct((), jnp.int32))

    @staticmethod
    def axes() -> "KVCache":
        a = Axes(lx.LAYERS, lx.DECODE_BATCH, lx.CACHE_SEQ, lx.KV_HEADS, lx.HEAD_DIM)
        return KVCache(a, a, Axes())


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _project_qkv(h, p, cfg: ModelConfig, positions):
    """q, k, v of ``h``. Every attention path scales scores by
    1/sqrt(head_dim); a config's ``attention_multiplier`` is folded into q
    as its ratio to that scale (0.125 for granite's 1/64 at head_dim 64, a
    power of two, so the fold is exact)."""
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(h.dtype))
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"].astype(h.dtype))
    if cfg.pos_emb == "rope":
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
    if cfg.attention_multiplier is not None:
        q = q * jnp.asarray(cfg.attention_multiplier * q.shape[-1] ** 0.5,
                            q.dtype)
    return q, k, v


def _residual(x, y, cfg: ModelConfig):
    """``x`` plus the branch output ``y``, scaled by the config's
    ``residual_multiplier`` in float32 (granite's 0.22 is not a bf16
    number), then rounded once."""
    if cfg.residual_multiplier != 1.0:
        y = (y.astype(jnp.float32) * cfg.residual_multiplier).astype(y.dtype)
    return x + y


def _attn_train(x, p, cfg: ModelConfig, positions):
    h = L.rmsnorm(x, p["ln"], cfg.norm_eps)
    q, k, v = _project_qkv(h, p, cfg, positions)
    o = L.attention(q, k, v, impl=cfg.attn_impl, causal=True,
                    window=cfg.sliding_window)
    return _residual(x, jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype)),
                     cfg)


def _attn_decode(x, p, cfg: ModelConfig, ck, cv, pos):
    """x: (B,1,D); ck/cv: (B,Sc,Kh,Dh). Returns (x', ck', cv')."""
    h = L.rmsnorm(x, p["ln"], cfg.norm_eps)
    q, k, v = _project_qkv(h, p, cfg, pos[None, None] if pos.ndim == 0 else pos)
    s_cache = ck.shape[1]
    slot = pos % s_cache if cfg.sliding_window else pos
    ck = lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), slot, axis=1)
    cv = lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), slot, axis=1)
    kv_len = jnp.minimum(pos + 1, s_cache)
    # decode always uses the chunked backend: dynamic kv_len + grouped KV
    o = L.attention(q, ck, cv, impl="chunked", causal=False, window=None,
                    kv_len=kv_len)
    o = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    return _residual(x, o, cfg), ck, cv


def _ffn(x, lp, cfg: ModelConfig, is_moe: bool):
    h = L.rmsnorm(x, lp["ln_mlp"], cfg.norm_eps)
    if is_moe:
        cast = jax.tree.map(lambda a: a.astype(x.dtype), lp["moe"])
        o, aux = L.moe(h, cast, n_exp=cfg.moe_experts, top_k=cfg.moe_top_k,
                       capacity_factor=cfg.moe_capacity_factor, kind=cfg.mlp_type,
                       impl=cfg.moe_impl)
        return _residual(x, o, cfg), aux
    cast = jax.tree.map(lambda a: a.astype(x.dtype), lp["mlp"])
    return (_residual(x, L.mlp(h, cast, cfg.mlp_type), cfg),
            jnp.zeros((), jnp.float32))


def _superblock_train(cfg: ModelConfig):
    il = cfg.moe_interleave if cfg.moe_experts >= 2 else 1

    def fn(carry, blk):
        x, aux, positions = carry
        for j in range(il):
            lp = blk[f"l{j}"]
            x = _attn_train(x, jax.tree.map(lambda a: a.astype(x.dtype), lp["attn"]),
                            cfg, positions)
            x, a = _ffn(x, lp, cfg, _is_moe_layer(cfg, j))
            aux = aux + a
        return (x, aux, positions), None

    return fn


_REMAT_POLICIES = {
    "none": None,
    "full": None,  # checkpoint with default policy = save nothing
    "dots": "dots",
}


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def _embed(params, cfg: ModelConfig, tokens=None, embeds=None, positions=None):
    if embeds is None:
        x = params["embed"].astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)[tokens]
    else:
        x = embeds
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    if cfg.pos_emb == "sinusoidal":
        x = x + L.sinusoidal(positions, cfg.d_model).astype(x.dtype)
    return x


def _head(params, cfg: ModelConfig, x):
    logits = L.lm_head(x, params, cfg)
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return logits


def forward(params, cfg: ModelConfig, tokens=None, embeds=None,
            positions=None) -> tuple[jax.Array, jax.Array]:
    """Training/prefill forward. Returns (logits, moe_aux_loss)."""
    b, s = (tokens.shape if tokens is not None else embeds.shape[:2])
    if positions is None:
        positions = jnp.arange(s)[None, :]
    x = _embed(params, cfg, tokens, embeds, positions)
    body = _maybe_remat(_superblock_train(cfg), cfg)
    (x, aux, _), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32), positions),
                              params["blocks"])
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return _head(params, cfg, x), aux


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def prefill(params, cfg: ModelConfig, tokens=None, embeds=None, max_len: int | None = None):
    """Run the prompt, build the KV cache. Returns (last_logits, cache)."""
    b, s = (tokens.shape if tokens is not None else embeds.shape[:2])
    max_len = max_len or s
    positions = jnp.arange(s)[None, :]
    x = _embed(params, cfg, tokens, embeds, positions)
    il = cfg.moe_interleave if cfg.moe_experts >= 2 else 1
    s_cache = KVCache.cache_len(cfg, max_len)
    cdtype = jnp.bfloat16

    def block(carry, blk):
        x, aux = carry
        ks, vs = [], []
        for j in range(il):
            lp = blk[f"l{j}"]
            ap = jax.tree.map(lambda a: a.astype(x.dtype), lp["attn"])
            h = L.rmsnorm(x, ap["ln"], cfg.norm_eps)
            q, k, v = _project_qkv(h, ap, cfg, positions)
            o = L.attention(q, k, v, impl=cfg.attn_impl, causal=True,
                            window=cfg.sliding_window)
            x = _residual(x, jnp.einsum("bshk,hkd->bsd", o,
                                        ap["wo"].astype(o.dtype)), cfg)
            x, a = _ffn(x, lp, cfg, _is_moe_layer(cfg, j))
            aux = aux + a
            # cache tail of K/V (ring layout when windowed)
            if s >= s_cache:
                tail_k, tail_v = k[:, s - s_cache:], v[:, s - s_cache:]
                slots = (np.arange(s - s_cache, s) % s_cache)
                ck = jnp.zeros((b, s_cache, *k.shape[2:]), cdtype).at[:, slots].set(
                    tail_k.astype(cdtype))
                cv = jnp.zeros((b, s_cache, *v.shape[2:]), cdtype).at[:, slots].set(
                    tail_v.astype(cdtype))
            else:
                pad = s_cache - s
                ck = jnp.pad(k.astype(cdtype), ((0, 0), (0, pad), (0, 0), (0, 0)))
                cv = jnp.pad(v.astype(cdtype), ((0, 0), (0, pad), (0, 0), (0, 0)))
            ks.append(ck)
            vs.append(cv)
        return (x, aux), (jnp.stack(ks), jnp.stack(vs))

    (x, _aux), (k_all, v_all) = lax.scan(_maybe_remat(block, cfg),
                                         (x, jnp.zeros((), jnp.float32)),
                                         params["blocks"])
    # (n_super, il, B, S, K, D) -> (L, B, S, K, D)
    k_all = k_all.reshape(cfg.n_layers, *k_all.shape[2:])
    v_all = v_all.reshape(cfg.n_layers, *v_all.shape[2:])
    x = L.rmsnorm(x[:, -1:], params["ln_f"], cfg.norm_eps)
    logits = _head(params, cfg, x)[:, 0]
    cache = KVCache(k_all, v_all, jnp.asarray(s, jnp.int32))
    return logits, cache


def supports_paged(cfg: ModelConfig) -> bool:
    """True when this config can decode against a global KV page pool.

    Excluded: MoE only (capacity routing mixes tokens across batch rows, so
    a batched paged step would not be bit-independent per slot the way the
    vmapped lane step is). Sliding-window configs page too: their block
    tables are *rings* — ``decode_step_paged`` takes the window, the kernel
    reads ring tables, and the engine recycles pages that fall wholly
    outside the window, so a windowed slot holds O(window/page_size) pages
    (the paged rendition of the lane cache's ring layout).
    """
    return cfg.moe_experts < 2


def paged_pool_init(cfg: ModelConfig, n_pages: int, page_size: int,
                    dtype=jnp.bfloat16, layers: int | None = None):
    """Zeroed global KV arena: a (k, v) pair of (L·P, ps, Kh·Dh), where
    ``L`` is ``layers``, the layers that hold KV (default: every layer;
    a hybrid's attention layers only).

    One flat, lane-dense page space for every layer: layer ``l``'s page
    ``p`` is row ``l·P + p`` (``P = n_pages``), and a position's KV heads lie
    head-major along the last axis (head ``kh`` at lanes ``[kh·Dh,
    (kh+1)·Dh)``). Block tables hold page ids in ``[0, P)``;
    :func:`decode_step_paged` offsets them by ``l·P`` for layer ``l``, so
    each layer keeps its own copy of any null/sentinel page the caller
    reserves inside ``n_pages``. The last axis is a multiple of 128 lanes
    for the served widths, so the TPU keeps the arena row-major, the layout
    the Pallas kernel reads, and the step updates it in place. The pool
    carries no per-slot structure — block tables impose it per step.
    """
    shape = ((cfg.n_layers if layers is None else layers) * n_pages,
             page_size, cfg.n_kv_heads * cfg.resolved_head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def paged_attention_layer(x, attn, cfg: ModelConfig, pool_k, pool_v, tables,
                          lengths, *, append_mask=None, window=None,
                          impl: str = "ref", tp_axis: str | None = None):
    """One attention layer of a paged step, with its residual: x (B, 1, D)
    at positions ``lengths``; ``tables`` already offset to this layer's
    rows of the arena. Returns ``(x', pool_k', pool_v')``."""
    ap = jax.tree.map(lambda a: a.astype(x.dtype), attn)
    with jax.named_scope("attn.qkv"):
        h = L.rmsnorm(x, ap["ln"], cfg.norm_eps)
        q, k, v = _project_qkv(h, ap, cfg, lengths[:, None])
    # the append and the attention carry the scopes attn.append and
    # attn.kernel
    o, pool_k, pool_v = paged_ops.paged_decode_append(
        q[:, 0], k[:, 0], v[:, 0], pool_k, pool_v, tables, lengths,
        append_mask=append_mask, window=window, impl=impl)
    with jax.named_scope("attn.out"):
        if tp_axis is not None:
            # (B, H/tp, Dh) per device -> (B, H, Dh), heads in mesh
            # order = single-device order; wo is replicated, so the
            # projection below is bitwise the unsharded one
            o = lax.all_gather(o, tp_axis, axis=1, tiled=True)
        x = _residual(x, jnp.einsum("bshk,hkd->bsd", o[:, None],
                                    ap["wo"].astype(o.dtype)), cfg)
    return x, pool_k, pool_v


def decode_step_paged(params, cfg: ModelConfig, pool_k, pool_v, tables,
                      lengths, tokens, append_mask=None, impl: str | None = None,
                      window: int | None = None, tp_axis: str | None = None):
    """One serving step against the global page pool (no per-slot lanes).

    tokens (B,) int32; lengths (B,) int32 — positions already resident per
    slot (the new entry lands at ``lengths[b]``); tables (B, NP) int32 page
    ids in ``[0, P)``; pool_k/pool_v the flat arena (L·P, ps, Kh·Dh) of
    :func:`paged_pool_init`. ``append_mask`` (B,) bool gates
    the KV append per slot (False = the lane is idle/stalled and rides the
    batch; its logits are garbage and must be ignored). Returns
    ``(logits (B, V), pool_k', pool_v')`` — pools should be donated.

    ``window`` (defaulting to ``cfg.sliding_window``) switches the block
    tables to **ring** semantics: tables need only
    ``ceil(window/page_size) + 1`` entries, the tail entry wraps, and
    attention covers the last ``window`` positions — bit-identical to the
    lane backend's ring cache. Rope positions stay absolute (``lengths``),
    exactly as the lane decode computes them. Pass an explicit ``window``
    when the serving engine clamps it to the device cache length.

    Every per-slot quantity (rope position, KV length, page chain) is a
    batched vector, so one launch serves ragged slots; the attention itself
    is the fused paged kernel (``repro.kernels.paged_attention``), reading
    K/V in place from the pool through the block table. The layer scan
    carries the whole arena and hands layer ``l`` the tables offset by
    ``l·P``: the append scatters into the carried arena in place and the
    kernel reads it there, so no layer's pool is sliced out, copied or
    stacked back. ``impl`` (None)
    follows the platform: the compiled Pallas kernel on a TPU, the
    bit-exact ``ref`` gather elsewhere; pass it only to pit one against
    the other.

    ``tp_axis`` names the mesh axis this step runs tensor-parallel over
    (inside ``shard_map``): params arrive head-sharded (wq/wk/wv slices),
    the pool arena holds this device's KV-head slice, and the per-device
    attention outputs are all-gathered along the head axis right before
    the (replicated) output projection — the step's only collective. Each
    query head's attention touches only its own KV head, so the gathered
    head block is bitwise the single-device one; everything downstream of
    the gather is replicated compute. ``None`` (default) is the
    single-device path, bit-identical by construction.
    """
    from repro.kernels import backend

    if window is None:
        window = cfg.sliding_window
    if impl is None:
        impl = "pallas" if backend.on_tpu() else "ref"
    positions = lengths[:, None]
    x = _embed(params, cfg, tokens[:, None], None, positions)
    # supports_paged excludes MoE, so every superblock is one dense layer
    rows = pool_k.shape[0] // cfg.n_layers      # P: one layer's pages

    def block(carry, blk_and_layer):
        x, pk, pv = carry
        blk, layer = blk_and_layer
        lp = blk["l0"]
        x, pk, pv = paged_attention_layer(
            x, lp["attn"], cfg, pk, pv, tables + layer * rows, lengths,
            append_mask=append_mask, window=window, impl=impl,
            tp_axis=tp_axis)
        with jax.named_scope("mlp"):
            x, _ = _ffn(x, lp, cfg, False)
        return (x, pk, pv), None

    with jax.named_scope("layers"):
        (x, pool_k, pool_v), _ = lax.scan(
            block, (x, pool_k, pool_v),
            (params["blocks"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    with jax.named_scope("head"):
        x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
        logits = _head(params, cfg, x)[:, 0]
    return logits, pool_k, pool_v


def decode_step(params, cfg: ModelConfig, cache: KVCache, tokens):
    """One serving step: tokens (B,1) int32 -> (logits (B,V), cache')."""
    b = tokens.shape[0]
    pos = cache.pos
    x = _embed(params, cfg, tokens, None, pos[None, None])
    il = cfg.moe_interleave if cfg.moe_experts >= 2 else 1
    n_super = cfg.n_layers // il
    ck = cache.k.reshape(n_super, il, *cache.k.shape[1:])
    cv = cache.v.reshape(n_super, il, *cache.v.shape[1:])

    def block(carry, blk_and_cache):
        x, aux = carry
        blk, ck_b, cv_b = blk_and_cache
        ck_o, cv_o = [], []
        for j in range(il):
            lp = blk[f"l{j}"]
            ap = jax.tree.map(lambda a: a.astype(x.dtype), lp["attn"])
            x, ck_j, cv_j = _attn_decode(x, ap, cfg, ck_b[j], cv_b[j], pos)
            x, a = _ffn(x, lp, cfg, _is_moe_layer(cfg, j))
            aux = aux + a
            ck_o.append(ck_j)
            cv_o.append(cv_j)
        return (x, aux), (jnp.stack(ck_o), jnp.stack(cv_o))

    (x, _aux), (ck_new, cv_new) = lax.scan(block, (x, jnp.zeros((), jnp.float32)),
                                           (params["blocks"], ck, cv))
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = _head(params, cfg, x)[:, 0]
    new_cache = KVCache(ck_new.reshape(cache.k.shape), cv_new.reshape(cache.v.shape),
                        pos + 1)
    return logits, new_cache
