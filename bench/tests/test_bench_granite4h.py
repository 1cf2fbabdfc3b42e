"""granite-4.0-h-micro against its plain reference, at test size on the CPU.

The program (``Model.forward``; bulk prefill then decode through the
hybrid cache of SSD states and KV caches; ``ServeEngine``) agrees with
``bench/reference/granite4h.py`` on seeded random weights.  The score
cell's check is blind to none of the ways a layer can depart from the
published one: each single departure of the reference reads over the
cell's limits.  The reference's counts match the figures worked out by
hand, and the score cell's readers read the named SSD kernels.
"""
import json
import math
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
import granite_testkit  # noqa: E402
import trace_reduce as tr  # noqa: E402
from harness.cells import load_module  # noqa: E402
from harness.device import PEAKS  # noqa: E402
from harness.run_context import MetricContext  # noqa: E402

ref = load_module(BENCH, "reference", "granite4h")
driver = load_module(BENCH, "drivers", "score")
serve = load_module(BENCH, "drivers", "serve")
V5E = PEAKS["TPU v5 lite"]
CELL = "score.granite4h.8k"
with open(os.path.join(BENCH, "workloads", f"{CELL}.json")) as f:
    LIMITS = json.load(f)["limits"]
with open(os.path.join(BENCH, "configs", "granite-4.0-h-micro.json")) as f:
    FULL = json.load(f)
TINY = dict(granite_testkit.TINY_GRANITE, dtype="float32")
LENGTH = 64


def _model(cfg):
    import dataclasses
    from repro.configs.base import get_arch
    from repro.models.model import build_model
    return build_model(dataclasses.replace(get_arch(cfg["program_arch"]),
                                           **ref.program_fields(cfg)))


@pytest.fixture(scope="module")
def weights():
    w = jax.jit(lambda k: ref.make_weights(TINY, k))(jax.random.PRNGKey(1))
    return jax.tree.map(lambda x: x.astype(jnp.float32), w)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(2), (2, LENGTH), 0,
                              TINY["vocab_size"])


@pytest.fixture(scope="module")
def ref_logits(weights, tokens):
    h = jax.jit(lambda w, t: ref.hidden(w, TINY, t))(weights, tokens)
    return jnp.matmul(h, weights["embed"].T, precision="highest") \
        / TINY["logits_scaling"]


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want))) / float(jnp.max(jnp.abs(want)))


def test_program_forward_matches_reference(weights, tokens, ref_logits):
    model = _model(TINY)
    with jax.default_matmul_precision("highest"):
        full, _ = jax.jit(model.forward)(ref.to_program(weights), tokens)
    assert _rel(full, ref_logits) < 1e-5
    # logits spread over the vocabulary: a flat softmax would hide errors
    spread = jnp.max(ref_logits, -1) - jnp.mean(ref_logits, -1)
    assert 3.0 < float(jnp.mean(spread)) < 30.0


def test_blocked_state_recurrence_matches_token_by_token(weights, tokens):
    seq = jax.jit(lambda w, t: ref.hidden(w, TINY, t))(weights, tokens)
    blk = jax.jit(lambda w, t: ref.hidden(w, TINY, t, block=16))(weights,
                                                                   tokens)
    assert _rel(blk, seq) < 1e-5


def test_prefill_then_decode_matches_reference(weights, tokens, ref_logits):
    """Bulk prefill of the prompt, then one token at a time, through the
    hybrid cache (SSD conv and state beside full KV caches)."""
    model = _model(TINY)
    params = ref.to_program(weights)
    prompt = 40
    with jax.default_matmul_precision("highest"):
        cache = model.init_cache(2, LENGTH, dtype=jnp.float32)
        steps = tokens.T[:prompt]
        cache = jax.jit(model.prefill)(
            params, steps, cache,
            jnp.broadcast_to(jnp.arange(prompt)[:, None], steps.shape),
            jnp.ones(steps.shape, bool))
        step = jax.jit(model.decode_step)
        got = []
        for t in range(prompt, LENGTH):
            logits, cache = step(params, tokens[:, t:t + 1], cache,
                                 jnp.full((2, 1), t))
            got.append(logits[:, 0])
    assert _rel(jnp.stack(got, 1), ref_logits[:, prompt:]) < 1e-5


def test_serve_engine_serves_the_reference_argmax(weights):
    """Greedy tokens that ``ServeEngine`` serves (chunked prefill, then
    decode through the hybrid cache): at every served position the
    reference's logit of the served token is its best."""
    from repro.serve.engine import ServeEngine
    model = _model(TINY)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, TINY["vocab_size"], n, dtype=np.int32)
               for n in (11, 20)]
    engine = ServeEngine(model, ref.to_program(weights), max_batch=2,
                         max_len=48, prefill_chunk=8)
    with jax.default_matmul_precision("highest"):
        for p in prompts:
            engine.submit(p, max_new_tokens=12)
        done = engine.run()
    assert engine.prefill_calls > 0
    for req in done:
        seq = np.concatenate([req.prompt, np.asarray(req.output, np.int32)])
        assert len(req.output) == 12
        h = ref.hidden(weights, TINY, jnp.asarray(seq[None, :-1]))
        logits = jnp.matmul(h[0], weights["embed"].T, precision="highest")
        served = logits[len(req.prompt) - 1:]
        picked = jnp.take_along_axis(
            served, jnp.asarray(req.output)[:, None], axis=-1)[:, 0]
        gap = jnp.max(served, axis=-1) - picked
        assert float(jnp.max(gap)) < 1e-4


def test_gaps_read_zero_on_the_reference_argmax(weights, tokens,
                                                ref_logits):
    """The serving check's interface: the gap below the reference's best
    logit, 0 for its own argmax, and the spread for its argmin."""
    best = jnp.argmax(ref_logits, axis=-1).astype(jnp.int32)
    worst = jnp.argmin(ref_logits, axis=-1).astype(jnp.int32)
    fn = jax.jit(ref.gaps, static_argnums=(1, 4, 5))
    cfg = serve._Frozen(TINY)
    gap, ctl = fn(weights, cfg, tokens, best, True, 32)
    assert float(jnp.max(gap)) < 1e-4
    assert ctl.shape == gap.shape and float(jnp.min(ctl)) >= 0.0
    gap, _ = fn(weights, cfg, tokens, worst, False, 32)
    np.testing.assert_allclose(
        gap, jnp.max(ref_logits, -1) - jnp.min(ref_logits, -1), rtol=1e-4)


def _zero(key):
    def edit(w):
        return dict(w, layers=[dict(lw, **{key: jnp.zeros_like(lw[key])})
                               if key in lw else lw for lw in w["layers"]])
    return edit


# each a single departure from the published layer, in the configuration
# or the weights the reference is given
DEPARTURES = {
    "no_d_skip": ({}, _zero("d")),
    "no_conv_bias": ({}, _zero("conv_b")),
    "rotary_on": ({"position_embedding_type": "rope"}, None),
    "embedding_times_sqrt_d": (
        {"embedding_multiplier": math.sqrt(TINY["hidden_size"])}, None),
    "softmax_scale_rsqrt_head_dim": (
        {"attention_multiplier": 1.0 / math.sqrt(
            TINY["hidden_size"] // TINY["num_attention_heads"])}, None),
    "no_residual_multiplier": ({"residual_multiplier": 1.0}, None),
}


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


@pytest.mark.parametrize("departure", sorted(DEPARTURES))
def test_departure_reads_over_the_cell_limits(weights, tokens,
                                              program_scores, departure):
    """The program computes the published layer; a reference that departs
    from it in one way reads over at least one of the cell's limits."""
    got, positions = program_scores
    fields, edit = DEPARTURES[departure]
    cfg = dict(TINY, **fields)
    w = edit(weights) if edit else weights
    numbers = driver._compare(got, _reference_scores(w, cfg, tokens,
                                                     positions))
    assert any(numbers[name] > limit for name, limit in LIMITS.items()), \
        numbers


def test_control_fails_where_the_program_passes(tmp_path):
    root = kit.make_root(str(tmp_path))
    result, outcome = kit.run_cell(root, granite_testkit.CELL[0],
                                   seed=2 ** 33 + 5, seconds=0.3,
                                   control=True)
    assert result["correct"] is True
    assert set(outcome.controls) == set(result["check"])
    assert any(outcome.controls[name] > c["limit"]
               for name, c in result["check"].items())
    assert outcome.readings["tokens_per_call"] == \
        granite_testkit.SETTINGS["length"]


def test_counts_match_the_hand_figures():
    p = ref.param_counts(FULL)
    assert p["mamba"] == 2048 * 8512 + 4096 * 2048          # in + out proj
    assert (p["mamba"] + p["mlp"]) / 1e6 == pytest.approx(76.2, abs=0.05)
    assert (p["attention"] + p["mlp"]) / 1e6 == pytest.approx(60.8, abs=0.05)
    assert p["embed"] / 1e6 == pytest.approx(205.5, abs=0.05)
    total = 36 * (p["mamba"] + p["mlp"]) + 4 * (p["attention"] + p["mlp"]) \
        + p["embed"]
    assert total / 1e9 == pytest.approx(3.19, abs=0.005)
    # 2 flops a parameter a position: ~6.38 GFLOP a token of matmuls
    assert 2 * total / 1e9 == pytest.approx(6.38, abs=0.01)
    flops, nbytes = ref.counts(FULL, 8192)
    assert nbytes / 1e9 == pytest.approx(6.38, abs=0.01)   # bf16 weights
    attention = 4 * 2 * 8192 ** 2 * 2048       # causal q k^T and p v
    ssd = 36 * 5 * 64 * 128 * 64 * 8192        # state update and readout
    assert flops == pytest.approx(2 * total * 8192 + attention + ssd)
    assert flops / 1e12 == pytest.approx(54.1, abs=0.1)


def test_program_fields_are_the_registered_config():
    from repro.configs.base import get_arch
    registered = get_arch("granite-4.0-h-micro")
    for field, value in ref.program_fields(FULL).items():
        assert getattr(registered, field) == value, field


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
    shape = "f32[64,8192,64]{2,1,0}"
    ops = [_kernel("ssd_chunk.1", shape, 2e-3), _kernel("ssd_carry.1", shape,
                                                       1e-3),
           _kernel("flash_attention_pallas.1", shape, 5e-3),
           _kernel("scan_linrec.1", shape, 7e-3)]
    trace = tr.Reduced(window=(0.0, 2.0), devices=[0],
                       busy={0: [(0.0, 1.5)]}, ops=ops, modules=[], spans=[])
    nbytes = 2 * 64 * 8192 * 64 * 4
    want = 100 * 2 * nbytes / V5E["hbm_bytes_per_s"] / 3e-3
    assert _read("pallas_roofline.ssd", trace) == pytest.approx(want)
    assert _read("idle_pct.score", trace) == pytest.approx(25.0)
    readings = {"traced_calls": 3, "tokens_per_call": 8192}
    flops, _ = ref.counts(FULL, 8192)
    assert _read("mfu_pct.score", trace, readings) == pytest.approx(
        100 * 3 * flops / 2.0 / V5E["bf16_flops_per_s"])
    # a program that names no SSD kernel, or a run that traced no call
    unnamed = tr.Reduced(window=(0.0, 2.0), devices=[0], busy={0: []},
                         ops=ops[2:], modules=[], spans=[])
    assert _read("pallas_roofline.ssd", unnamed) is None
    assert _read("mfu_pct.score", trace, {}) is None
