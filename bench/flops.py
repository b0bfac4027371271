"""Model FLOPs of a served token, in closed form from a configuration file.

A token fed at context length ``n`` (it attends over ``n`` positions)
costs two FLOPs per weight of every matrix product it passes through —
the q, k, v and o projections, the three SwiGLU matrices of every layer,
and the output head — plus ``4 · heads · head_dim · n`` per layer for
the scores and the weighted sum. Embedding lookups and elementwise work
are not counted.
"""

from __future__ import annotations

from bench.spec import head_dim


def matmul_weights(cfg: dict) -> int:
    """Weights a token multiplies through (output head included)."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 head_dim(cfg))
    per_layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * cfg["intermediate_size"]
    return L * per_layer + d * cfg["vocab_size"]


def tokens_flops(cfg: dict, tokens: int, attended: int) -> float:
    """FLOPs of ``tokens`` fed tokens that attend over ``attended``
    positions in all."""
    L, h, hd = cfg["num_hidden_layers"], cfg["num_attention_heads"], head_dim(cfg)
    return 2.0 * matmul_weights(cfg) * tokens + 4.0 * L * h * hd * attended
