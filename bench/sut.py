"""The system under test: the program's engine, built from a config file.

This is the only module of the benchmark that imports the program
(``src/repro``). It maps a configuration file onto the program's
``ModelConfig`` and builds one ``ContinuousBatchingEngine`` on the paged
backend with the program's defaults, except slots, ``max_len`` and page
size, which the file gives.
"""

from __future__ import annotations

import sys
import time

from bench.spec import ROOT, head_dim, norm_eps

sys.path.insert(0, str(ROOT / "src"))

from repro.models.config import ModelConfig  # noqa: E402
from repro.serve.engine import ContinuousBatchingEngine, Request  # noqa: E402

__all__ = ["ContinuousBatchingEngine", "Request", "build_engine",
           "model_config"]

# equations the program has, as a configuration file states them; any
# other value is a model the program cannot serve
_FIXED = {"hidden_act": "silu", "norm_type": "rmsnorm",
          "partial_rotary_factor": 1.0, "embedding_multiplier": 1.0,
          "residual_multiplier": 1.0, "logits_scaling": 1.0,
          "torch_dtype": "bfloat16"}


def model_config(cfg: dict) -> ModelConfig:
    for key, want in _FIXED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{cfg['name']}: {key}={cfg[key]!r}; the "
                             f"program serves only {want!r}")
    hd = head_dim(cfg)
    if abs(cfg.get("attention_multiplier", hd ** -0.5) - hd ** -0.5) > 1e-12:
        raise ValueError(f"{cfg['name']}: the program scales attention "
                         "scores by 1/sqrt(head_dim) only")
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=hd,
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        mlp_type="swiglu", pos_emb="rope", rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"], norm_eps=norm_eps(cfg))


def build_engine(cfg: dict, params) -> ContinuousBatchingEngine:
    e = cfg["engine"]
    return ContinuousBatchingEngine(
        model_config(cfg), params, slots=e["slots"], max_len=e["max_len"],
        page_size=e["page_size"], clock=time.perf_counter)
