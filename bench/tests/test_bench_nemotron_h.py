"""NVIDIA-Nemotron-3-Nano-30B-A3B against its plain reference, at test size
on the CPU.

The program (``Model.forward`` over the unperiodic stack: grouped
Mamba-2, the expert-share MoE, NoPE attention, an untied head) agrees with
``bench/reference/nemotron_h.py`` on seeded random weights, with a held
share of 4 of 8 experts that does not start at expert 0.  The shares of
all chips add up to the uncut layer.  The score cell's check is blind to
none of the ways a layer can depart from the published one: each single
departure of the reference reads over the cell's limits.  The
reference's counts match the figures worked out by hand, and the cell's
readers read the named kernels.
"""
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import bench_testkit as kit  # noqa: E402
import nemotron_testkit  # noqa: E402
import trace_reduce as tr  # noqa: E402
from harness.cells import load_module  # noqa: E402
from harness.device import PEAKS  # noqa: E402
from harness.run_context import MetricContext  # noqa: E402

ref = load_module(BENCH, "reference", "nemotron_h")
driver = load_module(BENCH, "drivers", "score")
serve = load_module(BENCH, "drivers", "serve")
V5E = PEAKS["TPU v5 lite"]
CELL = "score.nemotron3nano.8k"
with open(os.path.join(BENCH, "workloads", f"{CELL}.json")) as f:
    LIMITS = json.load(f)["limits"]
FULL = nemotron_testkit.FULL
TINY = dict(nemotron_testkit.TINY_NEMOTRON, dtype="float32")
LENGTH = 64


def _model(cfg, **fields):
    import dataclasses
    from repro.configs.base import get_arch
    from repro.models.model import build_model
    return build_model(dataclasses.replace(get_arch(cfg["program_arch"]),
                                           **ref.program_fields(cfg),
                                           **fields))


@pytest.fixture(scope="module")
def weights():
    w = jax.jit(lambda k: ref.make_weights(TINY, k))(jax.random.PRNGKey(1))
    return jax.tree.map(lambda x: x.astype(jnp.float32), w)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(2), (2, LENGTH), 0,
                              TINY["vocab_size"])


def _logits(w, cfg, tokens, **kw):
    h = jax.jit(lambda w, t: ref.hidden(w, cfg, t, **kw))(w, tokens)
    return jnp.matmul(h, w["lm_head"].T, precision="highest")


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want))) / float(jnp.max(jnp.abs(want)))


def test_tiny_config_has_every_mechanism():
    d = ref.dims(TINY)
    assert set(ref.kinds(TINY)) == {"mamba", "moe", "attn"}
    assert d["G"] > 1 and d["HELD"] < d["E"] and d["OFF"] > 0


@pytest.mark.parametrize("use_pallas,length", [(False, LENGTH), (True, 256)],
                         ids=["xla", "pallas-interpret"])
def test_program_forward_matches_reference(weights, use_pallas, length):
    """The XLA path, and the Pallas kernels in interpret mode (the SSD
    launches reading a head's B/C group through their index maps, the
    grouped matmuls of the expert share)."""
    t = jax.random.randint(jax.random.PRNGKey(2), (1, length), 0,
                           TINY["vocab_size"])
    model = _model(TINY, use_pallas=use_pallas)
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(model.forward)(ref.to_program(weights), t)
    want = _logits(weights, TINY, t)
    assert _rel(got, want) < 1e-5
    spread = jnp.max(want, -1) - jnp.mean(want, -1)
    assert 2.0 < float(jnp.mean(spread)) < 30.0


def test_blocked_state_recurrence_matches_token_by_token(weights, tokens):
    seq = jax.jit(lambda w, t: ref.hidden(w, TINY, t))(weights, tokens)
    blk = jax.jit(lambda w, t: ref.hidden(w, TINY, t, block=16))(weights,
                                                                   tokens)
    assert _rel(blk, seq) < 1e-5


def test_shares_add_up_to_the_uncut_layer(weights):
    """Each chip's expert share of one MoE layer, the shared expert counted
    once, summed over the 2 shares of 4 experts, is the reference's layer
    with all 8 experts held."""
    from repro.models.moe import expert_share_block
    lw = next(lw for lw in weights["layers"] if "router" in lw)
    uncut = dict(TINY, n_routed_experts=8, held_experts_offset=0)
    wide = ref.make_weights(uncut, jax.random.PRNGKey(3))
    lw8 = next(lw for lw in wide["layers"] if "router" in lw)
    lw8 = dict(jax.tree.map(lambda x: x.astype(jnp.float32), lw8),
               router=lw["router"], bias=lw["bias"])
    h = jax.random.normal(jax.random.PRNGKey(4), (2, LENGTH, TINY["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        want = ref._moe_mixer(h, lw8, ref.dims(uncut), uncut, False)
        got = 0.0
        for share, off in enumerate((0, 4)):
            cfg = dict(TINY, held_experts_offset=off)
            model = _model(cfg)
            p = {"router": {"w": lw8["router"]}, "router_bias": lw8["bias"],
                 "wu": lw8["up"][off:off + 4], "wo": lw8["down"][off:off + 4],
                 "shared": {"wu": {"w": lw8["shared_up"]},
                            "wo": {"w": lw8["shared_down"] * (share == 0)}}}
            got = got + expert_share_block(p, h, model.cfg, jnp.float32)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def program_scores(weights, tokens):
    model = _model(TINY)
    positions = jnp.asarray([0, 17, 40, LENGTH - 1])

    def score(params, t):
        logits, _ = model.forward(params, t)
        lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        ll = jnp.take_along_axis(lp, t[:, 1:, None], axis=-1)[..., 0]
        return ll, logits[:, positions]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(score)(ref.to_program(weights), tokens)
    return got, positions


_score = jax.jit(ref.score, static_argnums=(1, 4, 5, 6))


def _reference_scores(w, cfg, tokens, positions):
    return _score(w, serve._Frozen(cfg), tokens, positions, False, 16, 32)


def test_program_reads_under_the_cell_limits(weights, tokens,
                                             program_scores):
    got, positions = program_scores
    numbers = driver._compare(got, _reference_scores(weights, TINY, tokens,
                                                     positions))
    assert all(numbers[name] < 1e-4 for name in LIMITS)


def _softmax_pick(logits, bias, k):
    w, picked = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return w / jnp.sum(w, axis=-1, keepdims=True), picked


def _biased_weights_pick(logits, bias, k):
    biased = jax.nn.sigmoid(logits) + bias
    w, picked = jax.lax.top_k(biased, k)
    return w / jnp.sum(w, axis=-1, keepdims=True), picked


def _whole_norm(y, z, delta, groups, eps):
    return ref._rms(y * jax.nn.silu(z), delta, eps)


def _shared_bc(v, heads):
    return jnp.repeat(v[:, :, :1], heads, axis=2)


# each a single departure from the published layer, as a replacement of
# one of the reference's functions
DEPARTURES = {
    "softmax_routing": ("pick", _softmax_pick),
    "weights_from_biased_scores": ("pick", _biased_weights_pick),
    "gated_norm_over_all_channels": ("gated_norm", _whole_norm),
    "bc_shared_by_all_heads": ("per_head", _shared_bc),
}


@pytest.mark.parametrize("departure", sorted(DEPARTURES))
def test_departure_reads_over_the_cell_limits(weights, tokens,
                                              program_scores, departure,
                                              monkeypatch):
    """The program computes the published layer; a reference that departs
    from it in one way reads over at least one of the cell's limits."""
    got, positions = program_scores
    name, fn = DEPARTURES[departure]
    monkeypatch.setattr(ref, name, fn)
    want = jax.jit(lambda w, t, p: ref.score(w, TINY, t, p, False, 16, 32))(
        weights, tokens, positions)
    numbers = driver._compare(got, want)
    assert any(numbers[n] > limit for n, limit in LIMITS.items()), numbers


def test_control_fails_where_the_program_passes(tmp_path):
    """The tiny cell through the harness, served in bfloat16 as the
    configuration states: correct under its limits, which the float8
    control exceeds."""
    root = kit.make_root(str(tmp_path))
    nemotron_testkit.add_to(root)
    result, outcome = kit.run_cell(root, nemotron_testkit.CELL[0],
                                   seed=2 ** 33 + 5, seconds=0.3,
                                   control=True)
    assert result["correct"] is True, result["check"]
    assert set(outcome.controls) == set(result["check"])
    assert any(outcome.controls[name] > c["limit"]
               for name, c in result["check"].items())


def test_counts_match_the_hand_figures():
    p = ref.param_counts(FULL)
    assert p["mamba"] == 2688 * 10304 + 4096 * 2688     # in + out proj
    assert p["attention"] == 2688 * (32 + 4) * 128 + 4096 * 2688
    assert p["expert"] == 2 * 2688 * 1856
    assert p["shared"] == 2 * 2688 * 3712
    held = 23 * (8 * p["expert"] + p["shared"] + p["router"]) \
        + 23 * p["mamba"] + 6 * p["attention"] + 2 * p["embed"]
    assert held / 1e9 == pytest.approx(3.69, abs=0.005)     # this chip
    assert ref.routed_rows(FULL, 8192) == 8192 * 6 * 8 / 128   # 384 a expert
    flops, nbytes = ref.counts(FULL, 8192)
    assert nbytes / 1e9 == pytest.approx(7.37, abs=0.01)    # bf16 weights
    dense = 23 * (p["mamba"] + p["router"] + p["shared"]) \
        + 6 * p["attention"] + p["embed"]
    routed = 23 * 2 * 3072 * p["expert"]
    attention = 6 * 2 * 8192 ** 2 * 4096
    ssd = 23 * 5 * 64 * 128 * 64 * 8192
    assert flops == pytest.approx(2 * dense * 8192 + routed + attention
                                  + ssd)
    assert flops / 1e12 == pytest.approx(32.6, abs=0.1)
    moe_flops, moe_bytes = ref.moe_counts(FULL, 8192)
    assert moe_flops == pytest.approx(routed)
    assert moe_bytes == pytest.approx(
        23 * 2 * (8 * p["expert"] + 3072 * 2 * (2688 + 1856)))


def test_program_fields_are_the_registered_config_but_the_share():
    """The configuration runs the registered architecture with every
    published width; only the held experts and vocabulary rows (the keys
    listed in ``reduced``) and their share's offset differ."""
    from repro.configs.base import get_arch
    registered = get_arch("nemotron-3-nano-30b-a3b")
    share = {"vocab": 65536, "moe_held": 8, "moe_held_offset": 0}
    for field, value in ref.program_fields(FULL).items():
        if field in share:
            assert value == share[field], field
        else:
            assert getattr(registered, field) == value, field
    assert registered.vocab == FULL["vocab_size_published"] == 131072
    assert registered.n_experts == FULL["n_routed_experts_published"] == 128
    assert registered.moe_held == 0                      # all of them


def _kernel(name, shape, dur):
    hlo = (f"%{name} = {shape} custom-call({shape} %x), "
           'custom_call_target="tpu_custom_call"')
    return tr.Op(0, "jit_score", hlo, 0.0, dur)


def _read(metric, trace, readings=None):
    cell = types.SimpleNamespace(config=FULL, settings={},
                                 module=lambda kind, name: load_module(
                                     BENCH, kind, name))
    ctx = MetricContext(trace=trace, readings=readings or {}, peaks=V5E,
                        cell=cell)
    return load_module(BENCH, "metrics", metric).read(ctx)


def test_score_readers_on_a_synthetic_trace():
    shape = "bf16[50176,1856]{1,0}"
    ops = [_kernel("moe_gmm_up.3", shape, 2e-3),
           _kernel("moe_gmm_down.7", shape, 3e-3),
           _kernel("ssd_chunk.1", "f32[64,8192,64]{2,1,0}", 4e-3),
           _kernel("flash_attention_pallas.1", shape, 5e-3)]
    trace = tr.Reduced(window=(0.0, 2.0), devices=[0],
                       busy={0: [(0.0, 1.5)]}, ops=ops, modules=[], spans=[])
    readings = {"traced_calls": 3, "tokens_per_call": 8192}
    flops, nbytes = ref.moe_counts(FULL, 8192)
    least = 3 * max(flops / V5E["bf16_flops_per_s"],
                    nbytes / V5E["hbm_bytes_per_s"])
    assert _read("roofline_pct.moe", trace, readings) == pytest.approx(
        100 * least / 5e-3)
    total, _ = ref.counts(FULL, 8192)
    assert _read("mfu_pct.score", trace, readings) == pytest.approx(
        100 * 3 * total / 2.0 / V5E["bf16_flops_per_s"])
    assert _read("pallas_roofline.ssd", trace) is not None
    # a program that names no grouped-matmul launch, or no traced call
    unnamed = tr.Reduced(window=(0.0, 2.0), devices=[0], busy={0: []},
                         ops=ops[2:], modules=[], spans=[])
    assert _read("roofline_pct.moe", unnamed, readings) is None
    assert _read("roofline_pct.moe", trace, {}) is None
