"""Analytic parameter counting for MODEL_FLOPS (no tensor allocation)."""
from __future__ import annotations

from repro.configs.base import ModelConfig
from repro.models.layers import UNGATED


def _attn_params(cfg: ModelConfig) -> int:
    d, hd = cfg.d_model, cfg.head_dim
    return (d * cfg.n_heads * hd          # wq
            + 2 * d * cfg.n_kv_heads * hd  # wk, wv
            + cfg.n_heads * hd * d)        # wo


def _mlp_params(d: int, f: int, activation: str = "swiglu") -> int:
    if activation in UNGATED:
        return 2 * d * f                   # up, down
    return 3 * d * f                       # gate, up, down


def _ssd_params(cfg: ModelConfig) -> int:
    d = cfg.d_model
    n_heads = cfg.ssm_heads or cfg.ssm_expand * d // cfg.ssm_head_dim
    d_inner = n_heads * cfg.ssm_head_dim
    bc = 2 * cfg.ssm_groups * cfg.ssm_state
    return (d * (2 * d_inner + bc + n_heads)                   # in_proj
            + d_inner * d)                                     # out_proj


def _hybrid_params(cfg: ModelConfig, experts: int) -> int:
    """Matmul parameters of a hybrid stack, with ``experts`` routed experts
    counted in each MoE layer; with d_ff every other layer has its MLP."""
    d, act = cfg.d_model, cfg.activation
    w = cfg.lru_width or d
    moe = (d * cfg.n_experts + experts * _mlp_params(d, cfg.d_ff_expert, act)
           + _mlp_params(d, cfg.d_ff_shared or cfg.d_ff_expert
                         * cfg.n_shared_experts, act))
    mixer = {"rec": 2 * d * w + 2 * w * w + w * d,
             "ssd": _ssd_params(cfg), "attn": _attn_params(cfg), "moe": moe}
    mlp = _mlp_params(d, cfg.d_ff) if cfg.d_ff else 0
    groups = cfg.n_layers // max(len(cfg.block_pattern), 1)
    return groups * sum(mixer[k] + (mlp if k != "moe" else 0)
                        for k in cfg.block_pattern)


def active_param_count(cfg: ModelConfig) -> int:
    """Matmul-active parameters per token (MoE: only routed-in experts)."""
    d = cfg.d_model
    per_layer = 0
    if cfg.family in ("dense", "audio", "vlm"):
        per_layer = _attn_params(cfg) + _mlp_params(d, cfg.d_ff)
        n_layers = cfg.n_layers
        if cfg.family == "audio":
            n_layers = (cfg.n_enc_layers or cfg.n_layers) + \
                (cfg.n_dec_layers or cfg.n_layers)
            # decoder cross-attention
            per_layer += _attn_params(cfg) * (cfg.n_dec_layers or
                                              cfg.n_layers) // max(n_layers, 1)
        total = per_layer * n_layers
        if cfg.family == "vlm" and cfg.cross_attn_every:
            total += _attn_params(cfg) * (cfg.n_layers // cfg.cross_attn_every)
        return total
    if cfg.family == "moe":
        per_layer = _attn_params(cfg)
        per_layer += cfg.moe_top_k * _mlp_params(d, cfg.d_ff_expert)
        per_layer += cfg.n_shared_experts * _mlp_params(d, cfg.d_ff_expert)
        per_layer += d * cfg.n_experts     # router
        return per_layer * cfg.n_layers
    if cfg.family == "ssm":
        per_layer = _ssd_params(cfg)
        if cfg.d_ff:
            per_layer += _mlp_params(d, cfg.d_ff)
        return per_layer * cfg.n_layers
    if cfg.family == "hybrid":
        return _hybrid_params(cfg, cfg.moe_top_k)
    raise ValueError(cfg.family)


def audio_split_params(cfg: ModelConfig):
    """(encoder_params, decoder_params) for enc-dec MODEL_FLOPS."""
    d = cfg.d_model
    enc_layer = _attn_params(cfg) + _mlp_params(d, cfg.d_ff)
    dec_layer = 2 * _attn_params(cfg) + _mlp_params(d, cfg.d_ff)  # + cross
    n_enc = cfg.n_enc_layers or cfg.n_layers
    n_dec = cfg.n_dec_layers or cfg.n_layers
    return enc_layer * n_enc, dec_layer * n_dec


def total_param_count(cfg: ModelConfig) -> int:
    """All parameters incl. embeddings and full expert banks."""
    d = cfg.d_model
    total = cfg.vocab * d                  # tied embedding
    if not cfg.tie_embeddings:
        total += cfg.vocab * d             # the lm_head
    if cfg.family == "hybrid":
        return total + _hybrid_params(cfg, cfg.n_experts)
    if cfg.family == "moe":
        per_layer = _attn_params(cfg)
        per_layer += cfg.n_experts * _mlp_params(d, cfg.d_ff_expert)
        per_layer += cfg.n_shared_experts * _mlp_params(d, cfg.d_ff_expert)
        per_layer += d * cfg.n_experts
        return total + per_layer * cfg.n_layers
    return total + active_param_count(cfg)
