"""granite-4.0-h-micro in the program: registered at its published widths
and layer pattern; the fields that carry its layer (Mamba-2 D skip, conv
bias and dt-scaled input; NoPE attention with a configured softmax scale;
the embedding, residual and logits multipliers) default to the layer the
other configurations compute; and the hybrid group runs the same through
``forward`` and through bulk prefill and decode over its cache."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, all_archs, get_arch
from repro.launch.params import total_param_count
from repro.models import attention as attn_mod
from repro.models.model import build_model

ARCH = "granite-4.0-h-micro"
NEW_FIELDS = {"ssm_dt_input": False, "ssm_d_skip": False,
              "ssm_conv_bias": False, "use_rope": True, "attn_scale": 0.0,
              "embed_scale": 0.0, "residual_scale": 1.0,
              "logits_scaling": 1.0}


def test_registered_with_the_published_widths_and_pattern():
    cfg = get_arch(ARCH)
    kinds = list(cfg.block_pattern) * (cfg.n_layers // len(cfg.block_pattern))
    assert len(kinds) == cfg.n_layers == 40
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [5, 15, 25, 35]
    assert kinds.count("ssd") == 36
    assert (cfg.d_model, cfg.vocab, cfg.d_ff) == (2048, 100352, 8192)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 64)
    d_inner = cfg.ssm_expand * cfg.d_model
    assert (d_inner // cfg.ssm_head_dim, cfg.ssm_head_dim, cfg.ssm_state,
            cfg.conv_width) == (64, 64, 128, 4)
    assert cfg.ssm_dt_input and cfg.ssm_d_skip and cfg.ssm_conv_bias
    assert not cfg.use_rope and cfg.attn_scale == 1 / 64
    assert (cfg.embed_scale, cfg.residual_scale, cfg.logits_scaling) == \
        (12.0, 0.22, 8.0)
    assert (cfg.norm_eps, cfg.param_dtype, cfg.compute_dtype) == \
        (1e-5, "bfloat16", "bfloat16")
    assert total_param_count(cfg) / 1e9 == pytest.approx(3.19, abs=0.005)


def test_new_fields_default_to_the_layer_the_other_configs_compute():
    for field, value in NEW_FIELDS.items():
        assert getattr(ModelConfig(arch="x", family="dense"), field) == value
    for arch in all_archs():
        if arch == ARCH:
            continue
        cfg = get_arch(arch)
        assert {f: getattr(cfg, f) for f in NEW_FIELDS} == NEW_FIELDS, arch
        if cfg.family in ("ssm", "hybrid"):
            shapes = jax.eval_shape(build_model(cfg.reduced()).init,
                                    jax.random.PRNGKey(0))
            paths = {jax.tree_util.keystr(p) for p, _ in
                     jax.tree_util.tree_leaves_with_path(shapes)}
            assert not any("d_skip" in p or "conv_b" in p for p in paths)


def _tiny():
    return get_arch(ARCH).reduced()


def test_hybrid_forward_matches_prefill_and_decode():
    cfg = _tiny()
    assert cfg.block_pattern == get_arch(ARCH).block_pattern
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, L, prompt = 2, 24, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0, cfg.vocab)
    full, _ = jax.jit(model.forward)(params, tokens)
    cache = model.init_cache(B, L, dtype=jnp.float32)
    steps = tokens.T[:prompt]
    cache = jax.jit(model.prefill)(
        params, steps, cache,
        jnp.broadcast_to(jnp.arange(prompt)[:, None], steps.shape),
        jnp.ones(steps.shape, bool))
    step = jax.jit(model.decode_step)
    outs = []
    for t in range(prompt, L):
        logits, cache = step(params, tokens[:, t:t + 1], cache,
                             jnp.full((B, 1), t))
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    rel = float(jnp.max(jnp.abs(full[:, prompt:] - dec))) / float(
        jnp.max(jnp.abs(full)))
    assert rel < 1e-4, rel


def test_multipliers_scale_what_they_name():
    """embed_scale replaces sqrt(d_model), logits_scaling divides the
    logits, residual_scale scales each layer's output before the add."""
    cfg = dataclasses.replace(_tiny(), n_layers=2,
                              block_pattern=("ssd", "attn"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab)
    base, _ = model.forward(params, tokens)
    half, _ = build_model(dataclasses.replace(
        cfg, logits_scaling=16.0)).forward(params, tokens)
    np.testing.assert_allclose(half, base / 2, rtol=1e-6)
    # with no layer output added, the stream is the scaled embedding alone
    quiet = dataclasses.replace(cfg, residual_scale=1e-30, logits_scaling=1.0)
    table = params["embed"]["table"]
    for scale, want in ((0.0, math.sqrt(cfg.d_model)), (3.0, 3.0)):
        got, _ = build_model(dataclasses.replace(
            quiet, embed_scale=scale)).forward(params, tokens)
        x = table[tokens] * want
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + cfg.norm_eps)
        x = x * (1 + params["final_norm"])
        np.testing.assert_allclose(got, x @ table.T, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_nope_attention_with_a_configured_scale(use_pallas):
    """No rotary and the configured softmax scale, on the XLA path and the
    flash kernel's (interpreted) path alike."""
    cfg = dataclasses.replace(_tiny(), use_pallas=use_pallas)
    p = attn_mod.init_attention(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, L = 1, 128
    x = jax.random.normal(jax.random.PRNGKey(1), (B, L, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    got, _ = attn_mod.self_attention(p, x, cfg, positions=pos,
                                     compute_dtype=jnp.float32)
    hd, rep = cfg.head_dim, cfg.n_heads // cfg.n_kv_heads
    q = (x @ p["wq"]["w"]).reshape(B, L, cfg.n_heads, hd)
    k = jnp.repeat((x @ p["wk"]["w"]).reshape(B, L, -1, hd), rep, axis=2)
    v = jnp.repeat((x @ p["wv"]["w"]).reshape(B, L, -1, hd), rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * cfg.attn_scale
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    want = o.reshape(B, L, -1) @ p["wo"]["w"]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # decode of the last position over a KV cache of the others reads
    # the same scale
    kv = cfg.n_kv_heads
    cache = {"k": k[:, :, ::rep].at[:, -1].set(0.0),
             "v": v[:, :, ::rep].at[:, -1].set(0.0)}
    assert cache["k"].shape == (B, L, kv, hd)
    out, _ = attn_mod.self_attention(p, x[:, -1:], cfg,
                                     positions=pos[:, -1:], cache=cache,
                                     compute_dtype=jnp.float32)
    np.testing.assert_allclose(out[:, 0], want[:, -1], rtol=2e-5, atol=2e-5)
