"""granite-4.0-h-micro [ssm_hybrid]: Mamba-2 with a GQA layer every tenth.
[hf:ibm-granite/granite-4.0-h-micro config.json; hf] — 40L d_model=2048,
layer_types mmmmmammmm x4 (36 Mamba-2, 4 attention at 5/15/25/35);
Mamba-2 64 heads x 64, d_state 128, 1 group, conv 4 with bias; attention
32H (kv=8) head_dim 64, no positions (nope); SwiGLU 8192 after every
layer; RMSNorm eps 1e-5; vocab 100352, tied; multipliers embedding 12,
attention 1/64, residual 0.22, logits 1/8. Full attention layers:
long_500k skipped."""

from repro.models.config import ModelConfig

_PATTERN = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="ssm_hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
    d_ff=8192, vocab=100352, mlp_type="swiglu", pos_emb="none",
    tie_embeddings=True, norm_eps=1e-5, layer_types=_PATTERN * 4,
    ssm_state=128, ssm_head_dim=64, ssm_expand=2, ssm_conv_width=4,
    ssm_chunk=256, embedding_multiplier=12.0, attention_multiplier=0.015625,
    residual_multiplier=0.22, logits_scaling=8.0,
)


def smoke() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-smoke", family="ssm_hybrid",
        n_layers=6, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab=256, mlp_type="swiglu", pos_emb="none",
        tie_embeddings=True, norm_eps=1e-5,
        layer_types=("mamba", "attention", "mamba") * 2,
        ssm_state=16, ssm_head_dim=16, ssm_expand=2, ssm_conv_width=4,
        ssm_chunk=8, embedding_multiplier=12.0, attention_multiplier=0.0625,
        residual_multiplier=0.22, logits_scaling=8.0,
        q_block=8, kv_block=8, remat="none",
    )
