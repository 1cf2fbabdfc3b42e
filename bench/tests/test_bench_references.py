"""The benchmark's plain references against the program at small sizes
(CPU; Pallas kernels in interpret mode), and its algorithmic counts
against the figures worked out by hand."""
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import counts  # noqa: E402
from harness.cells import load_module  # noqa: E402
from harness.device import PEAKS  # noqa: E402

ops_ref = load_module(BENCH, "reference", "prefix_ops")
mamba_ref = load_module(BENCH, "reference", "mamba2")
V5E = PEAKS["TPU v5 lite"]
T = 2 ** 26


def _program(op):
    from repro.kernels.fft.ops import fft
    from repro.kernels.scan.ops import linear_recurrence, prefix_sum
    from repro.kernels.tridiag.ops import solve
    return {
        "prefix_sum.ks": functools.partial(prefix_sum, variant="ks",
                                           use_pallas=True, interpret=True),
        "prefix_sum.lf": functools.partial(prefix_sum, variant="lf",
                                           use_pallas=True, interpret=True),
        "linear_recurrence": functools.partial(
            linear_recurrence, use_pallas=True, interpret=True),
        "fft": functools.partial(fft, interpret=True),
        "tridiag.pcr": functools.partial(solve, variant="pcr",
                                         interpret=True),
    }[op]


@pytest.mark.parametrize("op", sorted(ops_ref.REFERENCE))
def test_op_reference_matches_program(op):
    n, batch = 128, 16
    args = ops_ref.make_inputs(ops_ref.INPUT_KIND[op], n, batch,
                               jax.random.PRNGKey(3))
    got = _program(op)(*args)
    want = ops_ref.REFERENCE[op](*args)
    assert float(ops_ref.rel_err(got, want)) < 1e-5
    # the bf16 control is far outside what the program reads
    ctl = ops_ref.control(op, args)
    assert float(ops_ref.rel_err(ctl, want)) > 1e-3


def test_sequential_references_are_exact_on_known_answers():
    x = jnp.arange(1.0, 9.0)[None]
    np.testing.assert_array_equal(ops_ref.cumsum(x)[0],
                                  np.cumsum(np.arange(1.0, 9.0)))
    a = jnp.full((1, 4), 0.5)
    b = jnp.ones((1, 4))
    np.testing.assert_allclose(ops_ref.linrec_sequential(a, b)[0],
                               [1.0, 1.5, 1.75, 1.875])
    ab = ops_ref.make_inputs("tridiag", 8, 2, jax.random.PRNGKey(0))
    x = ops_ref.thomas(*ab)
    a, b, c, d = ab
    ax = (a * jnp.pad(x, ((0, 0), (1, 0)))[:, :-1] + b * x
          + c * jnp.pad(x, ((0, 0), (0, 1)))[:, 1:])
    np.testing.assert_allclose(ax, d, atol=1e-5)


TINY = {"d_model": 32, "n_layer": 2, "vocab_size": 100, "vocab_rows": 112,
        "d_state": 8, "d_conv": 4, "expand": 2, "headdim": 16,
        "norm_eps": 1e-5, "dtype": "float32"}


def _program_model(cfg):
    import dataclasses
    from repro.configs.base import get_arch
    from repro.models.model import build_model
    fields = dict(mamba_ref.program_fields(cfg),
                  param_dtype="float32", compute_dtype="float32")
    return build_model(dataclasses.replace(get_arch("mamba2-130m"),
                                           **fields))


def test_mamba2_reference_matches_program_forward_and_decode():
    cfg = TINY
    w = jax.tree.map(lambda x: x.astype(jnp.float32),
                     mamba_ref.make_weights(cfg, jax.random.PRNGKey(1)))
    model = _program_model(cfg)
    params = mamba_ref.to_program(w)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0,
                                cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = jnp.matmul(mamba_ref.hidden(w, cfg, tokens), w["embed"].T)
        full, _ = model.forward(params, tokens)
        cache = model.init_cache(2, 32, dtype=jnp.float32)
        steps = []
        for t in range(tokens.shape[1]):
            logits, cache = model.decode_step(
                params, tokens[:, t:t + 1], cache, jnp.full((2, 1), t))
            steps.append(logits[:, 0])
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(full - want))) / scale < 1e-4
    dec = jnp.stack(steps, axis=1)
    assert float(jnp.max(jnp.abs(dec - want))) / scale < 1e-4


def test_mamba2_gaps_read_zero_on_the_reference_argmax():
    cfg = TINY
    w = mamba_ref.make_weights(cfg, jax.random.PRNGKey(4))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0,
                                cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        logits = jnp.matmul(mamba_ref.hidden(w, cfg, tokens),
                            w["embed"].astype(jnp.float32).T)
    best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    worst = jnp.argmin(logits, axis=-1).astype(jnp.int32)
    gap, ctl = mamba_ref.gaps(w, cfg, tokens, best, control=True, block=8)
    assert float(jnp.max(gap)) == 0.0
    assert ctl.shape == gap.shape and float(jnp.min(ctl)) >= 0.0
    gap, _ = mamba_ref.gaps(w, cfg, tokens, worst, block=8)
    np.testing.assert_allclose(
        gap, jnp.max(logits, -1) - jnp.min(logits, -1), rtol=1e-5)
    masked = jnp.full_like(best, -1)
    gap, _ = mamba_ref.gaps(w, cfg, tokens, masked, block=8)
    assert float(jnp.max(gap)) == 0.0


def test_byte_floors_match_the_hand_figures():
    floors = {op: counts.least_time_s(op, n, T // n, V5E) * 1e3
              for op, n in (("prefix_sum.ks", 1024),
                            ("linear_recurrence", 1024), ("fft", 4096),
                            ("tridiag.pcr", 1024))}
    assert floors["prefix_sum.ks"] == pytest.approx(0.6555, abs=1e-3)
    assert floors["linear_recurrence"] == pytest.approx(0.9832, abs=1e-3)
    assert floors["fft"] == pytest.approx(1.3110, abs=1e-3)
    assert floors["tridiag.pcr"] == pytest.approx(1.6386, abs=1e-3)
    # bound by bytes: the FFT's flops take under 1/64 of its byte time
    flops, nbytes = counts.op_counts("fft", 4096, T // 4096)
    assert flops / V5E["bf16_flops_per_s"] < nbytes / V5E["hbm_bytes_per_s"] / 64


def test_mamba2_counts_match_the_hand_figures():
    with open(os.path.join(BENCH, "configs", "mamba2-130m.json")) as f:
        cfg = json.load(f)
    state = counts.mamba2_state_bytes_per_lane(cfg)
    # 24 x 24 x 128 x 64 x 4 B SSD state + 24 x 3 x 1792 x 4 B conv window
    assert state == 24 * 24 * 128 * 64 * 4 + 24 * 3 * 1792 * 4
    assert state / 1e6 == pytest.approx(19.4, abs=0.05)
    assert counts.mamba2_flops_per_token(cfg) / 1e9 == pytest.approx(
        0.28, abs=0.01)
    assert counts.mamba2_flops_per_token(cfg, logits=False) < \
        counts.mamba2_flops_per_token(cfg)
