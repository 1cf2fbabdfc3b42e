"""SSD (Mamba-2) and RG-LRU kernels vs sequential oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.rglru.ops import rglru
from repro.kernels.rglru.ref import rglru_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_chunked_ref, ssd_ref

KEY = jax.random.PRNGKey(0)


def _ssd_inputs(B=2, L=256, H=2, P=16, S=8, G=1):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (B, L, H, P))
    a = jax.random.uniform(ks[1], (B, L, H), minval=0.85, maxval=0.999)
    b = jax.random.normal(ks[2], (B, L, G, S)) * 0.3
    c = jax.random.normal(ks[3], (B, L, G, S)) * 0.3
    return x, a, b, c


@pytest.mark.parametrize("chunk", [32, 64, 128])
def test_ssd_chunked_ref_matches_sequential(chunk):
    x, a, b, c = _ssd_inputs()
    ref = ssd_ref(x, a, b, c)
    got = ssd_chunked_ref(x, a, b, c, chunk=chunk)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


# chunk 256 at the model widths (S 128, P 64) over four chunks
_PIPELINE_SHAPES = {256: dict(L=1024, P=64, S=128)}


@pytest.mark.parametrize("chunk", [64, 128, 256])
def test_ssd_pallas_pipeline(chunk):
    x, a, b, c = _ssd_inputs(**_PIPELINE_SHAPES.get(chunk, {}))
    ref = ssd_ref(x, a, b, c)
    got = ssd(x, a, b, c, config={"tile_n": chunk}, interpret=True)
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("groups", [1, 2, 4])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_grouped_ssd_matches_sequential(groups, use_pallas):
    """B and C in G groups, head h reading group h // (H / G): the op (the
    chunked XLA form, and the two launches in interpret mode, whose index
    maps read a head's group row) matches the sequential oracle, and
    differs from it when every head reads group 0."""
    x, a, b, c = _ssd_inputs(L=256, H=4, G=groups)
    ref = ssd_ref(x, a, b, c)
    got = ssd(x, a, b, c, config={"tile_n": 128}, interpret=True,
              use_pallas=use_pallas)
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-3)
    if groups > 1:
        shared = ssd_ref(x, a, jnp.repeat(b[:, :, :1], groups, axis=2),
                         jnp.repeat(c[:, :, :1], groups, axis=2))
        assert float(jnp.max(jnp.abs(got - shared))) > 0.1


def test_ssd_small_decay_no_nan_grads():
    x, a, b, c = _ssd_inputs()
    a = a * 0.01      # strong decay: exercises the masked-exp stability fix
    def loss(x):
        return jnp.sum(ssd_chunked_ref(x, a, b, c, chunk=64) ** 2)
    g = jax.grad(loss)(x)
    assert bool(jnp.all(jnp.isfinite(g)))


def test_rglru_matches_ref():
    ks = jax.random.split(KEY, 2)
    a = jax.random.uniform(ks[0], (2, 128, 16), minval=0.8, maxval=0.99)
    u = jax.random.normal(ks[1], (2, 128, 16))
    ref = rglru_ref(a, u)
    got = rglru(a, u, config={"rows_per_program": 8, "tile_n": 128,
                              "radix": 4, "unroll": 1}, interpret=True)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Config resolution and plan conformance
# ---------------------------------------------------------------------------

def _chunk_launches(rec):
    return [l for l in rec if l.name == "ssd-intra"]


def test_ssd_config_reaches_chunk_launch():
    """``ssd(config=...)`` picks the chunk the intra-chunk launch runs."""
    from repro.kernels.blocks import driver

    x, a, b, c = _ssd_inputs(L=512)
    for chunk in (128, 256):
        with driver.capture_launches() as rec:
            got = ssd(x, a, b, c, config={"tile_n": chunk},
                      interpret=True, use_pallas=True)
        np.testing.assert_allclose(got, ssd_ref(x, a, b, c),
                                   rtol=1e-3, atol=1e-3)
        (intra,) = _chunk_launches(rec)
        assert intra.block_shape == (1, chunk)
        assert intra.grid == (4, 512 // chunk)


def test_ssd_overrides_context_reaches_chunk_launch():
    from repro.kernels.blocks import driver
    from repro.tuning import overrides

    x, a, b, c = _ssd_inputs(L=512)
    with overrides(ssd={"tile_n": 256}):
        with driver.capture_launches() as rec:
            ssd(x, a, b, c, interpret=True, use_pallas=True)
    (intra,) = _chunk_launches(rec)
    assert intra.block_shape == (1, 256)


def _run_traced(op, cfg, n):
    """Run ``op`` under ``cfg`` at length ``n``; return its workload and
    the launches it executed."""
    from repro.core.space import Workload
    from repro.kernels.blocks import driver

    with driver.capture_launches() as rec:
        if op == "ssd":
            x, a, b, c = _ssd_inputs(L=n)
            B, L, H, _ = x.shape
            wl = Workload(op="ssd", n=L, batch=B * H, variant="chunked")
            ssd(x, a, b, c, config=cfg, interpret=True, use_pallas=True)
        else:
            ks = jax.random.split(KEY, 2)
            a = jax.random.uniform(ks[0], (2, n, 16), minval=0.8,
                                   maxval=0.99)
            u = jax.random.normal(ks[1], (2, n, 16))
            wl = Workload(op="rglru", n=n, batch=32)
            rglru(a, u, config=cfg, interpret=True, use_pallas=True)
    return wl, tuple(rec)


@pytest.mark.parametrize("op,cfg,n,kind", [
    ("ssd", {"tile_n": 128}, 128, "fused"),
    ("ssd", {"tile_n": 128}, 512, "two-phase"),
    ("rglru", {"tile_n": 128, "rows_per_program": 8, "radix": 2}, 256,
     "fused"),
    ("rglru", {"tile_n": 128, "rows_per_program": 8, "radix": 2}, 2**14,
     "multipass"),
], ids=["ssd-one-chunk", "ssd-four-chunks", "rglru-fused",
        "rglru-multipass"])
def test_executed_launches_equal_plan(op, cfg, n, kind):
    """Conformance: the executed launch list is exactly ``plan_for``'s,
    and the plan's HBM passes are its launches (the rglru gate runs in
    the scan kernel, so no pass goes unlaunched)."""
    from repro.kernels.blocks.plan import plan_for
    from repro.tuning import normalizer_for

    wl, executed = _run_traced(op, cfg, n)
    plan = plan_for(wl, normalizer_for(op)(cfg, wl, None)
                    if op == "rglru" else cfg)
    assert plan.kind == kind
    assert executed == tuple(plan.launches)
    assert plan.passes == len(plan.launches)


def test_ssd_fused_handles_odd_chunk_count():
    """nc = 3: the sequential carry walks an odd chunk count in-kernel;
    the plan holds two launches and the output matches."""
    from repro.kernels.blocks.plan import plan_for

    wl, executed = _run_traced("ssd", {"tile_n": 128}, 384)
    assert executed == tuple(plan_for(wl, {"tile_n": 128}).launches)
    assert len(executed) == 2
    x, a, b, c = _ssd_inputs(L=384)
    got = ssd(x, a, b, c, config={"tile_n": 128}, interpret=True,
              use_pallas=True)
    np.testing.assert_allclose(got, ssd_ref(x, a, b, c), rtol=1e-3,
                               atol=1e-3)
