"""Per-arch smoke tests (reduced configs): forward shapes/finiteness +
decode-vs-forward consistency."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import SHAPES, all_archs, get_arch, shape_applicable
from repro.models.model import build_model

KEY = jax.random.PRNGKey(0)


def _memory_for(cfg, model, params, batch):
    if cfg.family == "audio":
        frames = jax.random.normal(KEY, (batch, cfg.enc_len, cfg.d_model))
        return model.encode(params, frames)
    if cfg.family == "vlm":
        return jax.random.normal(KEY, (batch, cfg.vision_len, cfg.d_model)) * 0.1
    return None


@pytest.mark.parametrize("arch", all_archs())
def test_forward_smoke(arch):
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(KEY)
    tokens = jax.random.randint(KEY, (2, 32), 0, cfg.vocab)
    memory = _memory_for(cfg, model, params, 2)
    logits, aux = model.forward(params, tokens, memory=memory)
    assert logits.shape == (2, 32, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ["gemma-2b", "mamba2-130m",
                                  "recurrentgemma-9b", "qwen3-moe-30b-a3b"])
def test_decode_matches_forward(arch):
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(KEY)
    B, L = 2, 12
    tokens = jax.random.randint(KEY, (B, L), 0, cfg.vocab)
    memory = _memory_for(cfg, model, params, B)
    full, _ = model.forward(params, tokens, memory=memory)
    cache = model.init_cache(B, max_len=L, dtype=jnp.float32)
    outs = []
    for t in range(L):
        pos = jnp.full((B, 1), t, jnp.int32)
        lg, cache = model.decode_step(params, tokens[:, t:t + 1], cache, pos,
                                      memory=memory)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    rel = float(jnp.max(jnp.abs(full - dec))) / (
        float(jnp.max(jnp.abs(full))) + 1e-9)
    assert rel < 2e-2, rel


def test_windowed_ring_buffer_cache():
    """Hybrid arch with window smaller than sequence: ring buffer correct."""
    import dataclasses
    cfg = dataclasses.replace(get_arch("recurrentgemma-9b").reduced(),
                              attn_window=8)
    model = build_model(cfg)
    params = model.init(KEY)
    B, L = 1, 24
    tokens = jax.random.randint(KEY, (B, L), 0, cfg.vocab)
    full, _ = model.forward(params, tokens)
    cache = model.init_cache(B, max_len=L, dtype=jnp.float32)
    outs = []
    for t in range(L):
        pos = jnp.full((B, 1), t, jnp.int32)
        lg, cache = model.decode_step(params, tokens[:, t:t + 1], cache, pos)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    rel = float(jnp.max(jnp.abs(full - dec))) / (
        float(jnp.max(jnp.abs(full))) + 1e-9)
    assert rel < 2e-2, rel


def test_shape_applicability_rules():
    n_skip = 0
    for arch in all_archs():
        cfg = get_arch(arch)
        ok, why = shape_applicable(cfg, SHAPES["long_500k"])
        if not ok:
            n_skip += 1
        else:
            assert cfg.sub_quadratic
    assert n_skip == 10     # exactly the 10 archs with full attention skip
