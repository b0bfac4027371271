"""Family dispatch: decls/forward/prefill/decode for any ModelConfig."""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp

from repro.models import griffin, hybrid, mamba2, transformer
from repro.models.config import ModelConfig

_TRANSFORMER_FAMILIES = ("dense", "moe", "audio", "vlm")


def _mod(cfg: ModelConfig):
    if cfg.family in _TRANSFORMER_FAMILIES:
        return transformer
    if cfg.family == "ssm":
        return mamba2
    if cfg.family == "hybrid":
        return griffin
    if cfg.family == "ssm_hybrid":
        return hybrid
    raise ValueError(f"unknown family {cfg.family}")


def decls(cfg: ModelConfig):
    return _mod(cfg).decls(cfg)


def forward(params, cfg: ModelConfig, tokens=None, embeds=None, positions=None):
    return _mod(cfg).forward(params, cfg, tokens=tokens, embeds=embeds,
                             positions=positions)


def prefill(params, cfg: ModelConfig, tokens=None, embeds=None, max_len=None):
    return _mod(cfg).prefill(params, cfg, tokens=tokens, embeds=embeds,
                             max_len=max_len)


def decode_step(params, cfg: ModelConfig, cache, tokens):
    return _mod(cfg).decode_step(params, cfg, cache, tokens)


def supports_paged(cfg: ModelConfig) -> bool:
    """True when the family can decode against a global KV page pool:
    transformer-family KV caches (see transformer.supports_paged) and the
    Mamba-2 hybrid, whose state rides in an arena of its own."""
    if cfg.family == "ssm_hybrid":
        return True
    if cfg.family not in _TRANSFORMER_FAMILIES:
        return False
    return transformer.supports_paged(cfg)


def holds_state(cfg: ModelConfig) -> bool:
    """True when the paged step carries per-slot recurrent state beside
    the KV arena (:func:`state_init`). Such a slot's KV pages say nothing
    of the state at their positions, so its prefixes are never shared."""
    return cfg.family == "ssm_hybrid"


def kv_layers(cfg: ModelConfig) -> int:
    """Layers whose KV the paged arena holds."""
    return hybrid.n_attention(cfg) if holds_state(cfg) else cfg.n_layers


def _require_paged(cfg: ModelConfig) -> None:
    # fail loudly, like the rest of the registry: a transformer-shaped KV
    # pool built from an SSM/Griffin config would be silently wrong
    if not supports_paged(cfg):
        raise ValueError(f"{cfg.name} ({cfg.family}) has no paged KV decode")


def paged_pool_init(cfg: ModelConfig, n_pages: int, page_size: int):
    _require_paged(cfg)
    return transformer.paged_pool_init(cfg, n_pages, page_size,
                                       layers=kv_layers(cfg))


def state_init(cfg: ModelConfig, slots: int):
    """Zeroed per-slot state arena of a config that :func:`holds_state`."""
    return hybrid.state_init(cfg, slots)


def state_reset(state, slot):
    """The state arena of :func:`state_init` with slot ``slot`` zeroed
    (donate it: in place)."""
    return hybrid.state_reset(state, slot)


def decode_step_paged(params, cfg: ModelConfig, pool_k, pool_v, tables,
                      lengths, tokens, append_mask=None, impl=None,
                      window=None, tp_axis=None, state=None):
    """One paged step: ``(logits, pool_k', pool_v')``. A config that
    :func:`holds_state` takes its state arena as ``state`` and returns it
    updated as a fourth output (it has no window and no tensor
    parallelism)."""
    _require_paged(cfg)
    if holds_state(cfg):
        return hybrid.decode_step_paged(params, cfg, pool_k, pool_v, state,
                                        tables, lengths, tokens,
                                        append_mask=append_mask, impl=impl)
    return transformer.decode_step_paged(params, cfg, pool_k, pool_v, tables,
                                         lengths, tokens,
                                         append_mask=append_mask, impl=impl,
                                         window=window, tp_axis=tp_axis)


def _cache_cls(cfg: ModelConfig):
    if cfg.family in _TRANSFORMER_FAMILIES:
        return transformer.KVCache
    return {"ssm": mamba2.SSMCache, "ssm_hybrid": hybrid.HybridCache,
            "hybrid": griffin.GriffinCache}[cfg.family]


def cache_abstract(cfg: ModelConfig, batch: int, max_len: int):
    return _cache_cls(cfg).abstract(cfg, batch, max_len)


def cache_init(cfg: ModelConfig, batch: int, max_len: int):
    return _cache_cls(cfg).init(cfg, batch, max_len)


def cache_axes(cfg: ModelConfig):
    return _cache_cls(cfg).axes()


def uses_token_inputs(cfg: ModelConfig) -> bool:
    """False for modality stubs whose train/prefill inputs are embeddings."""
    return cfg.embed_inputs
