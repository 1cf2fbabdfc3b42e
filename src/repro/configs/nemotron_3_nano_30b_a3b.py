"""nemotron-3-nano-30b-a3b: 52L d_model=2688 vocab=131072 (untied head), no
period: the pattern MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME of
single-mixer layers x + mixer(rmsnorm(x)) — M: Mamba-2 of 64 heads x 64,
8 B/C groups of state 128, conv 4 with bias, D skip, a gated norm per
group; E: 128 experts (relu2, no gate, width 1856) routed top-6 by sigmoid
scores plus a correction bias, weights normalised and x 2.5, one shared
expert of 3712; *: NoPE GQA of 32 / 2 heads x 128
[hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16]."""
from repro.configs.base import ModelConfig, register

KINDS = {"M": "ssd", "E": "moe", "*": "attn"}
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

CONFIG = register(ModelConfig(
    arch="nemotron-3-nano-30b-a3b", family="hybrid",
    n_layers=len(PATTERN), block_pattern=tuple(KINDS[c] for c in PATTERN),
    d_model=2688, n_heads=32, n_kv_heads=2, head_dim=128, vocab=131072,
    activation="relu2", norm_eps=1e-5, tie_embeddings=False,
    n_experts=128, moe_top_k=6, d_ff_expert=1856, n_shared_experts=1,
    d_ff_shared=3712, moe_router="sigmoid", moe_routed_scale=2.5,
    ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=8,
    conv_width=4, ssm_dt_input=True, ssm_d_skip=True, ssm_conv_bias=True,
    use_rope=False, embed_scale=1.0,
))
