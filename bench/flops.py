"""Model FLOPs of a served token, in closed form from a configuration file.

A token fed at context length ``n`` (it attends over ``n`` positions)
costs two FLOPs per weight of every matrix product it passes through,
plus the scores and the weighted sum of each layer that attends.
Embedding lookups and elementwise work are not counted. The closed forms
are the configuration's model file's (``bench/models/<equations>.py``).
"""

from __future__ import annotations

from bench import spec


def matmul_weights(cfg: dict) -> int:
    """Weights a token multiplies through (output head included)."""
    return spec.model(cfg).matmul_weights(cfg)


def tokens_flops(cfg: dict, tokens: int, attended: int) -> float:
    """FLOPs of ``tokens`` fed tokens that attend over ``attended``
    positions in all."""
    return spec.model(cfg).tokens_flops(cfg, tokens, attended)
