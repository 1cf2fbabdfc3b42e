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


def _ssd_inputs(B=2, L=256, H=2, P=16, S=8):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (B, L, H, P))
    a = jax.random.uniform(ks[1], (B, L, H), minval=0.85, maxval=0.999)
    b = jax.random.normal(ks[2], (B, L, S)) * 0.3
    c = jax.random.normal(ks[3], (B, L, S)) * 0.3
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
# Chain fusion + embedded-block config resolution
# ---------------------------------------------------------------------------

def test_ssd_override_reaches_embedded_phase_b():
    """Regression: the enclosing ssd resolution must be threaded into the
    embedded phase-B linrec block.  Before the fix, ``linrec_rows`` ran a
    fresh ``config=None`` resolution, so ``ssd(config=...)`` (and
    ``overrides(ssd=...)``) could never change the phase-B launch — here a
    radix override must flip its in-kernel stage decomposition."""
    from repro.kernels.blocks import driver

    x, a, b, c = _ssd_inputs(L=512)        # chunk 128 -> nc = 4
    traces = {}
    for radix in (2, 4):
        with driver.capture_launches() as rec:
            got = ssd(x, a, b, c,
                      config={"tile_n": 128, "radix": radix, "fuse": 0},
                      interpret=True, use_pallas=True)
        np.testing.assert_allclose(got, ssd_ref(x, a, b, c),
                                   rtol=1e-3, atol=1e-3)
        traces[radix] = [l for l in rec if l.name == "scan"]
    assert traces[2] and traces[4]
    assert traces[2][0].stages == (2, 2)    # nc = 4 under radix 2
    assert traces[4][0].stages == (4,)      # the override reached phase B


def test_ssd_overrides_context_reaches_embedded_phase_b():
    from repro.kernels.blocks import driver
    from repro.tuning import overrides

    x, a, b, c = _ssd_inputs(L=512)
    with overrides(ssd={"tile_n": 128, "radix": 4, "fuse": 0}):
        with driver.capture_launches() as rec:
            ssd(x, a, b, c, interpret=True, use_pallas=True)
    scans = [l for l in rec if l.name == "scan"]
    assert scans and scans[0].stages == (4,)


@pytest.mark.parametrize("op", ["ssd", "rglru"])
def test_fused_chain_issues_strictly_fewer_launches(op):
    """The fused chain must issue strictly fewer launches than the
    unfused one for at least this config (ssd: 3 -> 2 kernel launches;
    rglru: the multipass chain drops the XLA gate pass, counted through
    the plan since XLA ops don't appear in the Pallas launch trace)."""
    from repro.core.space import Workload
    from repro.kernels.blocks import driver
    from repro.kernels.blocks.plan import plan_for_chain

    traces = {}
    if op == "ssd":
        x, a, b, c = _ssd_inputs(L=512)
        for fuse in (0, 1):
            cfg = {"tile_n": 128, "radix": 2, "fuse": fuse}
            with driver.capture_launches() as rec:
                ssd(x, a, b, c, config=cfg, interpret=True, use_pallas=True)
            traces[fuse] = list(rec)
        assert len(traces[1]) < len(traces[0])
    else:
        ks = jax.random.split(KEY, 2)
        a = jax.random.uniform(ks[0], (2, 256, 16), minval=0.8, maxval=0.99)
        u = jax.random.normal(ks[1], (2, 256, 16))
        wl = Workload(op="rglru", n=256, batch=32)
        passes = {}
        for fuse in (0, 1):
            cfg = {"tile_n": 128, "rows_per_program": 8, "radix": 2,
                   "fuse": fuse}
            chain = plan_for_chain(wl, cfg)
            with driver.capture_launches() as rec:
                rglru(a, u, config=cfg, interpret=True, use_pallas=True)
            assert tuple(rec) == tuple(chain.launches)
            passes[fuse] = chain.plan.passes
        assert passes[1] < passes[0]


@pytest.mark.parametrize("fuse", [0, 1])
def test_ssd_executed_launches_equal_chain_plan(fuse):
    """Conformance: the executed launch list is exactly the chain plan's
    (dims pin the embedded phase-B geometry, so equality is structural)."""
    from repro.core.space import Workload
    from repro.kernels.blocks import driver
    from repro.kernels.blocks.plan import plan_for_chain

    x, a, b, c = _ssd_inputs(L=512)
    B, L, H, P = x.shape
    S = b.shape[-1]
    wl = Workload(op="ssd", n=L, batch=B * H, variant="chunked")
    cfg = {"tile_n": 128, "radix": 2, "fuse": fuse}
    chain = plan_for_chain(wl, cfg, dims=(S, P))
    with driver.capture_launches() as rec:
        ssd(x, a, b, c, config=cfg, interpret=True, use_pallas=True)
    assert tuple(rec) == tuple(chain.launches)


def test_ssd_fused_handles_odd_chunk_count():
    """nc = 3: unfused phase B has no valid linrec config (XLA fallback);
    the fused sequential carry runs in-kernel and must still match."""
    x, a, b, c = _ssd_inputs(L=384)
    ref = ssd_ref(x, a, b, c)
    for fuse in (0, 1):
        got = ssd(x, a, b, c, config={"tile_n": 128, "fuse": fuse},
                  interpret=True, use_pallas=True)
        np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-3)
