"""The MoE expert share and nemotron-3-nano-30b-a3b in the program.

The grouped matmul (Pallas, interpret mode) against jnp for uneven and
empty groups; the expert-share layer against a dense computation of the
same share, with no routed pair dropped however the pairs fall; the
registered configuration at its published widths and unperiodic layer
stack; decode through the caches (grouped Mamba-2 state, KV caches) equal
to the forward pass.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.kernels.matmul.ops import grouped_config, grouped_matmul
from repro.launch.params import total_param_count
from repro.models import moe
from repro.models.model import build_model

ARCH = "nemotron-3-nano-30b-a3b"
KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("group_tiles", [(1, 3, 2), (0, 2, 0, 3), (0, 0, 0)],
                         ids=["uneven", "empty-groups", "all-empty"])
@pytest.mark.parametrize("activation", [None, "relu2"])
def test_grouped_matmul_matches_jnp(group_tiles, activation):
    bm, k, n = 8, 128, 256
    tiles = jnp.asarray(group_tiles, jnp.int32)
    rows = (sum(group_tiles) + 2) * bm          # two tiles past the groups
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (rows, k))
    w = jax.random.normal(ks[1], (len(group_tiles), k, n)) / 16
    got = grouped_matmul(x, w, tiles, config={"block_m": bm, "block_n": 128},
                         activation=activation, use_pallas=True,
                         interpret=True)
    group = np.repeat(np.arange(len(group_tiles)), np.asarray(group_tiles)
                      * bm)
    used = len(group)
    want = jnp.einsum("mk,mkn->mn", x[:used], w[group],
                      precision="highest")
    if activation == "relu2":
        want = jnp.square(jnp.maximum(want, 0))
    np.testing.assert_allclose(got[:used], want, rtol=1e-5, atol=1e-5)
    xla = grouped_matmul(x, w, tiles, config={"block_m": bm,
                                              "block_n": 128},
                         activation=activation, use_pallas=False)
    np.testing.assert_allclose(xla[:used], want, rtol=1e-5, atol=1e-5)


def test_grouped_config_resolves_legal_blocks():
    """The widths of the published experts (1,856 and 2,688 columns) get
    lane-legal blocks within the scoped VMEM: the columns whole."""
    up = grouped_config(49152, 2688, 1856)
    down = grouped_config(49152, 1856, 2688, config={"block_m":
                                                     up["block_m"]})
    assert up["block_n"] == 1856 and down["block_n"] == 2688
    assert up["block_m"] == down["block_m"] and up["block_m"] % 8 == 0


def _share_cfg(**kw):
    base = dataclasses.replace(get_arch(ARCH).reduced(), param_dtype="float32",
                               compute_dtype="float32")
    return dataclasses.replace(base, **kw)


def _dense_share(p, x, cfg):
    """The same share computed densely: every held expert on every token,
    weighted by the token's routing weight for it (0 if not picked)."""
    _, w, idx = moe.route(p, x, cfg)
    held = p["wu"].shape[0]
    out = jnp.zeros_like(x)
    for e in range(held):
        we = jnp.sum(jnp.where(idx == cfg.moe_held_offset + e, w, 0.0), -1)
        h = jnp.square(jax.nn.relu(x @ p["wu"][e])) @ p["wo"][e]
        out = out + we[..., None] * h
    sh = p["shared"]
    return out + jnp.square(jax.nn.relu(x @ sh["wu"]["w"])) @ sh["wo"]["w"]


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("offset", [0, 4])
def test_expert_share_matches_dense_share(use_pallas, offset):
    cfg = _share_cfg(moe_held=4, moe_held_offset=offset, use_pallas=use_pallas)
    p = moe.init_expert_share(KEY, cfg, jnp.float32)
    p["router_bias"] = jax.random.uniform(jax.random.PRNGKey(5),
                                          (cfg.n_experts,), minval=-0.3,
                                          maxval=0.3)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = moe.expert_share_block(p, x, cfg, jnp.float32)
        want = _dense_share(p, x, cfg)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_no_routed_pair_is_dropped():
    """Every token picks the held experts (the bias makes them win): all
    T x k pairs land here, the most the layer can be sent, and each is
    computed."""
    cfg = _share_cfg(moe_held=4, moe_held_offset=2, use_pallas=True)
    p = moe.init_expert_share(KEY, cfg, jnp.float32)
    bias = jnp.zeros((cfg.n_experts,)).at[2:2 + cfg.moe_top_k].set(10.0)
    p["router_bias"] = bias
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 128, cfg.d_model))
    _, _, idx = moe.route(p, x.reshape(-1, cfg.d_model), cfg)
    assert bool(jnp.all((idx >= 2) & (idx < 2 + cfg.moe_top_k)))
    with jax.default_matmul_precision("highest"):
        got = moe.expert_share_block(p, x, cfg, jnp.float32)
        want = _dense_share(p, x, cfg)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_softmax_routing_unchanged_for_gshard_configs():
    """route() in softmax mode is the GShard block's routing: the top k of
    the softmax, renormalised, unscaled."""
    cfg = get_arch("qwen3-moe-30b-a3b").reduced()
    p = moe.init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model))
    probs, w, idx = moe.route(p, x, cfg)
    want = jax.nn.softmax(x @ p["router"]["w"], axis=-1)
    top, top_idx = jax.lax.top_k(want, cfg.moe_top_k)
    np.testing.assert_allclose(probs, want, rtol=1e-5)
    np.testing.assert_array_equal(idx, top_idx)
    np.testing.assert_allclose(w, top / top.sum(-1, keepdims=True),
                               rtol=1e-5)


def test_sigmoid_routing_weights_the_unbiased_scores():
    """route() in sigmoid mode picks the top k of score + correction bias
    and weights them by their unbiased scores, normalised and scaled: at
    the cell's size the logit gaps cannot tell these weights from the
    biased ones (PERF.md §2), so the layer is held to them here."""
    cfg = _share_cfg()
    p = moe.init_expert_share(KEY, cfg, jnp.float32)
    p["router_bias"] = jax.random.uniform(jax.random.PRNGKey(5),
                                          (cfg.n_experts,), minval=-0.3,
                                          maxval=0.3)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model))
    scores, w, idx = moe.route(p, x, cfg)
    want = jax.nn.sigmoid(jnp.matmul(x, p["router"]["w"],
                                     precision=jax.lax.Precision.HIGHEST))
    _, want_idx = jax.lax.top_k(want + p["router_bias"], cfg.moe_top_k)
    picked = jnp.take_along_axis(want, want_idx, axis=-1)
    np.testing.assert_allclose(scores, want, rtol=1e-5)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(
        w, cfg.moe_routed_scale * picked / picked.sum(-1, keepdims=True),
        rtol=1e-5)


def test_registered_with_the_published_widths_and_pattern():
    cfg = get_arch(ARCH)
    kinds = cfg.block_pattern
    assert len(kinds) == cfg.n_layers == 52
    assert (kinds.count("ssd"), kinds.count("moe"), kinds.count("attn")) \
        == (23, 23, 6)
    assert [i for i, k in enumerate(kinds) if k == "attn"] == \
        [5, 12, 19, 26, 33, 42]
    assert (cfg.d_model, cfg.vocab, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim) == (2688, 131072, 32, 2, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.conv_width) == (64, 64, 128, 8, 4)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.d_ff_expert, cfg.d_ff_shared,
            cfg.moe_routed_scale, cfg.moe_router) == \
        (128, 6, 1856, 3712, 2.5, "sigmoid")
    assert not cfg.tie_embeddings and cfg.embed_scale == 1.0
    assert not cfg.use_rope and cfg.activation == "relu2"
    assert total_param_count(cfg) / 1e9 == pytest.approx(31.58, abs=0.01)
    shapes = jax.eval_shape(build_model(cfg).init, KEY)
    assert sum(x.size for x in jax.tree.leaves(shapes)) / 1e9 == \
        pytest.approx(31.58, abs=0.01)


def test_reduced_keeps_every_layer_kind():
    cfg = get_arch(ARCH).reduced()
    assert cfg.block_pattern == ("ssd", "moe", "ssd", "moe", "ssd", "attn")
    assert cfg.n_layers == 6 and cfg.ssm_groups == 2


def test_decode_matches_forward():
    """Token by token through the caches (grouped Mamba-2 conv and state,
    KV caches; the MoE layers hold none) equals the forward pass."""
    cfg = _share_cfg(moe_held=4, moe_held_offset=2)
    model = build_model(cfg)
    params = model.init(KEY)
    B, L = 2, 12
    tokens = jax.random.randint(KEY, (B, L), 0, cfg.vocab)
    full, _ = jax.jit(model.forward)(params, tokens)
    cache = model.init_cache(B, max_len=L, dtype=jnp.float32)
    step = jax.jit(model.decode_step)
    outs = []
    for t in range(L):
        logits, cache = step(params, tokens[:, t:t + 1], cache,
                             jnp.full((B, 1), t))
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    rel = float(jnp.max(jnp.abs(full - dec))) / float(jnp.max(jnp.abs(full)))
    assert rel < 1e-4, rel
