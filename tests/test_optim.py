"""Optimizers, schedules, clipping, gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import (adafactor, adamw, clip_by_global_norm,
                         global_norm, warmup_cosine)
from repro.optim.compression import (compress, decompress, ef_roundtrip,
                                     psum_compressed)


def _quadratic_descends(make_opt):
    init, update = make_opt
    params = {"w": jnp.asarray([3.0, -2.0, 1.5]), "b": jnp.asarray(4.0)}
    state = init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2) + p["b"] ** 2
    l0 = float(loss(params))
    for _ in range(60):
        grads = jax.grad(loss)(params)
        params, state = update(grads, state, params)
    return l0, float(loss(params))


def test_adamw_descends():
    l0, l1 = _quadratic_descends(adamw(0.1, weight_decay=0.0))
    assert l1 < l0 * 0.05


def test_adafactor_descends():
    l0, l1 = _quadratic_descends(adafactor(0.3))
    assert l1 < l0 * 0.3


def test_adafactor_state_is_factored():
    init, _ = adafactor(0.1)
    params = {"w": jnp.zeros((64, 32))}
    st = init(params)
    assert st.vr["w"].shape == (64,)
    assert st.vc["w"].shape == (32,)


def test_warmup_cosine_shape():
    lr = warmup_cosine(1.0, 10, 100)
    assert float(lr(jnp.asarray(0))) == pytest.approx(0.0)
    assert float(lr(jnp.asarray(10))) == pytest.approx(1.0, rel=0.1)
    assert float(lr(jnp.asarray(100))) == pytest.approx(0.1, rel=0.05)


def test_clip_by_global_norm():
    grads = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(grads, 1.0)
    assert float(norm) == pytest.approx(20.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-4)


def test_compression_roundtrip_bounded():
    g = jnp.asarray(np.random.default_rng(0).normal(size=(256,)), jnp.float32)
    q, s, err = compress(g, jnp.zeros_like(g))
    deq = decompress(q, s)
    assert float(jnp.max(jnp.abs(deq - g))) <= float(s) + 1e-9


def test_error_feedback_accumulates():
    """With EF, the bias of repeated quantization vanishes in aggregate."""
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    err = jnp.zeros_like(g_true)
    total_applied = jnp.zeros_like(g_true)
    for _ in range(50):
        (applied,), (err,) = (lambda t: (list(t[0].values()),
                                         list(t[1].values())))(
            ef_roundtrip({"g": g_true}, {"g": err}))
        total_applied += applied
    # mean applied gradient ~ true gradient
    np.testing.assert_allclose(total_applied / 50, g_true, atol=1e-3)


def test_psum_compressed_single_device():
    mesh = jax.make_mesh((1,), ("pod",))
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    x = jnp.asarray([1.0, -2.0, 3.0])
    f = shard_map(lambda v: psum_compressed(v, "pod"), mesh=mesh,
                  in_specs=P(), out_specs=P())
    np.testing.assert_allclose(f(x), x, atol=0.05)
