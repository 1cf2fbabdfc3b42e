"""FFT kernels (Stockham Pallas + four-step) vs jnp.fft oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fft.kernel import fft_pallas
from repro.kernels.fft.ops import fft, ifft
from repro.kernels.fft.ref import fft_ref, four_step_ref, stockham_jnp

RNG = np.random.default_rng(1)


def _cx(batch, n):
    return jnp.asarray(RNG.normal(size=(batch, n))
                       + 1j * RNG.normal(size=(batch, n)), jnp.complex64)


@pytest.mark.parametrize("n", [64, 256, 1024])
@pytest.mark.parametrize("radix", [2, 4, 8, 16])
def test_stockham_kernel_all_radices(n, radix):
    x = _cx(4, n)
    got = fft(x, config={"radix": radix, "rows_per_program": 2, "tile_n": n},
              interpret=True)
    ref = fft_ref(x)
    err = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
    assert err < 1e-4, f"n={n} radix={radix}: {err}"


def test_mixed_radix_sizes():
    # 128 = 16 * 8: ragged final stage exercises the mixed-radix path
    x = _cx(2, 128)
    got = fft(x, config={"radix": 16, "rows_per_program": 2, "tile_n": 128},
              interpret=True)
    err = float(jnp.max(jnp.abs(got - fft_ref(x))))
    assert err < 1e-3


def test_four_step_large():
    x = _cx(2, 2**15)
    got = fft(x, config={"radix": 8, "rows_per_program": 2, "tile_n": 1024},
              interpret=True)
    ref = fft_ref(x)
    err = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
    assert err < 1e-4


def test_roundtrip():
    x = _cx(2, 512)
    cfg = {"radix": 4, "rows_per_program": 2, "tile_n": 512}
    rt = ifft(fft(x, config=cfg, interpret=True), config=cfg, interpret=True)
    assert float(jnp.max(jnp.abs(rt - x))) < 1e-4


def test_ref_formulations_agree():
    x = _cx(2, 1024)
    ref = fft_ref(x)
    for r in [2, 4, 8]:
        err = float(jnp.max(jnp.abs(stockham_jnp(x, r) - ref)))
        assert err < 1e-3
    err = float(jnp.max(jnp.abs(four_step_ref(x, 64) - ref)))
    assert err < 1e-3


def test_split_plane_kernel_direct():
    x = _cx(4, 256)
    re, im = jnp.real(x), jnp.imag(x)
    yre, yim = fft_pallas(re, im, rows_per_program=2, radix=4, interpret=True)
    ref = fft_ref(x)
    err = float(jnp.max(jnp.abs((yre + 1j * yim) - ref)))
    assert err < 1e-3


def _launch_records(launches):
    return [(launch.stages, launch.reorder) for launch in launches]


@pytest.mark.parametrize("n,radix,rows,inverse,want", [
    # the stage sequence each ops.fft_pcr size resolves
    (64, 8, 2, False, [((8, 8), "kernel")]),
    (128, 2, 2, False, [((2,) * 7, "kernel")]),
    (256, 16, 16, False, [((16, 16), "kernel")]),
    (512, 8, 2, False, [((8, 8, 8), "kernel")]),
    (1024, 4, 2, False, [((4,) * 5, "kernel")]),
    (2048, 2, 2, False, [((2,) * 11, "kernel")]),
    (4096, 16, 16, False, [((16, 16, 16), "kernel")]),
    # ragged last stages, at 128 and 256 rows (two groups of 128 lanes)
    (128, 16, 2, False, [((16, 8), "kernel")]),
    (256, 8, 128, False, [((8, 8, 4), "kernel")]),
    (128, 4, 256, False, [((4, 4, 4, 2), "kernel")]),
    (1024, 16, 16, True, [((16, 16, 4), "kernel")]),
    # four-step: column FFTs of 32, then row FFTs of 1024
    (2 ** 15, 8, 2, False, [((8, 4), "kernel"), ((8, 8, 8, 2), "kernel")]),
    # n not a power of two: the reversal stays in XLA
    (96, 8, 2, False, [((8, 6, 2), "xla")]),
])
def test_fft_launch_writes_natural_order(n, radix, rows, inverse, want):
    """Every launch returns natural order (checked against jnp.fft), and
    its ``Launch`` record says where the digit reversal ran."""
    from repro.kernels.blocks.driver import capture_launches
    x = _cx(max(rows, 2), n)
    config = {"radix": radix, "rows_per_program": rows,
              "tile_n": 1024 if n > 4096 else n}
    with capture_launches() as launches:
        got = fft(x, config=config, interpret=True, inverse=inverse)
    ref = jnp.fft.ifft(x) if inverse else fft_ref(x)
    err = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
    assert err < 1e-4, f"n={n} radix={radix} rows={rows}: {err}"
    assert _launch_records(launches) == want
