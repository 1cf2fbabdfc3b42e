"""granite-4.0-h-micro: 40L d_model=2048 vocab=100352 — Mamba-2 (64 heads x
64, state 128, conv bias, D skip) beside NoPE GQA attention (32 q / 8 kv
heads), pattern 5 ssd, 1 attn, 4 ssd, every layer with a SwiGLU MLP of
8192; muP multipliers: embedding x 12, residual x 0.22, logits / 8, softmax
scale 1/64 [hf:ibm-granite/granite-4.0-h-micro]."""
from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    arch="granite-4.0-h-micro", family="hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
    d_ff=8192, vocab=100352, activation="swiglu", norm_eps=1e-5,
    block_pattern=("ssd",) * 5 + ("attn",) + ("ssd",) * 4,
    ssm_state=128, ssm_head_dim=64, ssm_expand=2, conv_width=4,
    ssm_dt_input=True, ssm_d_skip=True, ssm_conv_bias=True,
    use_rope=False, attn_scale=0.015625,
    embed_scale=12.0, residual_scale=0.22, logits_scaling=8.0,
))
