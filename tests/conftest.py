"""Shared test plumbing: tolerance helpers, the kernel-vs-reference
differential case table, and deterministic hypothesis profiles.

Every ``kernels/*/ops.py`` entry point is registered once in
``KERNEL_CASES`` with its input builder and reference; the
``kernel_case`` fixture (via ``pytest_generate_tests``) fans the table
out over dtype x odd/prime shapes.  This replaces the per-file
copy-pasted size lists and per-file tolerance dances: a new tuned kernel
gets differential coverage by adding one table row, and a tolerance
change happens in exactly one place.

Hypothesis (optional dep): the ``ci`` profile pins a fixed derandomized
seed and disables deadlines so the property suites are deterministic on
shared CI runners — select it with ``HYPOTHESIS_PROFILE=ci``.
"""
from __future__ import annotations

import dataclasses
import os
import zlib
from typing import Callable, Sequence, Tuple

import numpy as np

try:    # optional dep — the property suites importorskip it themselves
    from hypothesis import HealthCheck, settings

    settings.register_profile(
        "ci", deadline=None, derandomize=True, max_examples=30,
        suppress_health_check=[HealthCheck.too_slow])
    settings.register_profile("dev", deadline=None)
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "dev"))
except ImportError:
    pass


# ---------------------------------------------------------------------------
# Shared tolerance helpers
# ---------------------------------------------------------------------------
# One tolerance per compute dtype. atol scales with the reference
# magnitude (prefix-style ops accumulate, so absolute error grows with
# the partial sums — a fixed atol either misses real bugs at small n or
# flakes at large n).

DTYPE_TOL = {
    "float32": (2e-5, 2e-5),
    "bfloat16": (2e-2, 2e-2),
    "complex64": (1e-4, 1e-4),
}


def assert_kernel_close(got, ref, dtype: str = "float32",
                        scale: float = 1.0) -> None:
    """Assert a kernel output matches its reference at the dtype's shared
    tolerance; ``scale`` loosens both bounds for ops with known extra
    error accumulation (multi-level tree reductions)."""
    rtol, atol_rel = DTYPE_TOL[str(dtype)]
    got = np.asarray(got)
    ref = np.asarray(ref)
    if np.iscomplexobj(ref):
        mag = float(np.max(np.abs(ref))) or 1.0
        err = float(np.max(np.abs(got - ref))) / mag
        assert err < rtol * scale, f"relative error {err:.3e}"
        return
    got = got.astype(np.float32)
    ref = ref.astype(np.float32)
    atol = atol_rel * max(float(np.max(np.abs(ref))), 1.0)
    np.testing.assert_allclose(got, ref, rtol=rtol * scale,
                               atol=atol * scale)


# ---------------------------------------------------------------------------
# Differential kernel-vs-reference case table
# ---------------------------------------------------------------------------
# Shapes deliberately include odd/prime batches (3, 5, 7 — e.g. a serve
# engine with 3 active slots) and non-power-of-two lengths: the config
# normalizers must fit tuned knobs to them and the kernels must still
# match their references bit-for-tolerance. Lengths stay even because
# the radix-based spaces have no valid config for odd n (asserted in
# test_kernels_differential.py, so the boundary is pinned, not implied).

ODD_BATCH_SHAPES: Tuple[Tuple[int, int], ...] = (
    (3, 256),    # prime batch, pow2 length
    (7, 96),     # prime batch, non-pow2 length (96 = 2^5 * 3)
    (5, 128),
)


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One (entry point, dtype, shape) differential check."""

    entry: str                       # ops.py entry-point name (test id)
    dtype: str
    batch: int
    n: int
    run: Callable[[str, int, int], None]   # (dtype, batch, n) -> asserts

    @property
    def id(self) -> str:
        return f"{self.entry}-{self.dtype}-b{self.batch}-n{self.n}"

    def __call__(self) -> None:
        self.run(self.dtype, self.batch, self.n)


def _rng(tag: str) -> np.random.Generator:
    # crc32, not hash(): string hashing is salted per process, and these
    # suites promise run-to-run reproducible inputs
    return np.random.default_rng(zlib.crc32(tag.encode()))


def _run_prefix_sum(dtype, batch, n):
    import jax.numpy as jnp

    from repro.kernels.scan.ops import prefix_sum
    from repro.kernels.scan.ref import scan_add_ref
    x = jnp.asarray(_rng(f"scan{batch}x{n}").normal(size=(batch, n)),
                    getattr(jnp, dtype))
    got = prefix_sum(x, interpret=True, use_pallas=True)
    assert_kernel_close(got, scan_add_ref(x), dtype)


def _run_linear_recurrence(dtype, batch, n):
    import jax.numpy as jnp

    from repro.kernels.scan.ops import linear_recurrence
    from repro.kernels.scan.ref import scan_linrec_assoc_ref
    rng = _rng(f"linrec{batch}x{n}")
    a = jnp.asarray(rng.uniform(0.8, 0.99, size=(batch, n)),
                    getattr(jnp, dtype))
    b = jnp.asarray(rng.normal(size=(batch, n)), getattr(jnp, dtype))
    got = linear_recurrence(a, b, interpret=True, use_pallas=True)
    assert_kernel_close(got, scan_linrec_assoc_ref(a, b), dtype)


def _run_tridiag(variant):
    def run(dtype, batch, n):
        import jax

        from repro.kernels.tridiag import ops
        from repro.kernels.tridiag.ref import random_system, thomas_ref
        a, b, c, d = random_system(jax.random.PRNGKey(batch * 1000 + n),
                                   batch, n)
        got = ops.solve(a, b, c, d, variant=variant)
        # diagonally-dominant solves are well conditioned but the parallel
        # eliminations reassociate heavily vs Thomas: shared f32 tol x50
        assert_kernel_close(got, thomas_ref(a, b, c, d), dtype, scale=50.0)
    return run


def _run_fft(dtype, batch, n):
    import jax.numpy as jnp

    from repro.kernels.fft.ops import fft
    from repro.kernels.fft.ref import fft_ref
    rng = _rng(f"fft{batch}x{n}")
    x = jnp.asarray(rng.normal(size=(batch, n))
                    + 1j * rng.normal(size=(batch, n)), jnp.complex64)
    got = fft(x, interpret=True)
    assert_kernel_close(got, fft_ref(x), dtype)


def _run_matmul(dtype, batch, n):
    import jax.numpy as jnp

    from repro.kernels.matmul.ops import matmul
    from repro.kernels.matmul.ref import matmul_ref
    rng = _rng(f"matmul{batch}x{n}")
    k = 65                      # prime inner dim
    a = jnp.asarray(rng.normal(size=(batch * 11, k)), getattr(jnp, dtype))
    b = jnp.asarray(rng.normal(size=(k, n)), getattr(jnp, dtype))
    got = matmul(a, b, interpret=True, use_pallas=True)
    assert_kernel_close(got, matmul_ref(a, b), dtype, scale=10.0)


def _run_ssd(dtype, batch, n):
    import jax

    from repro.kernels.ssd.ops import ssd
    from repro.kernels.ssd.ref import ssd_ref
    ks = jax.random.split(jax.random.PRNGKey(batch * 1000 + n), 4)
    x = jax.random.normal(ks[0], (batch, n, 2, 16))
    a = jax.random.uniform(ks[1], (batch, n, 2), minval=0.85, maxval=0.999)
    b = jax.random.normal(ks[2], (batch, n, 8)) * 0.3
    c = jax.random.normal(ks[3], (batch, n, 8)) * 0.3
    got = ssd(x, a, b, c, interpret=True)
    assert_kernel_close(got, ssd_ref(x, a, b, c), dtype, scale=10.0)


def _run_rglru(dtype, batch, n):
    import jax

    from repro.kernels.rglru.ops import rglru
    from repro.kernels.rglru.ref import rglru_ref
    ks = jax.random.split(jax.random.PRNGKey(batch * 1000 + n), 2)
    a = jax.random.uniform(ks[0], (batch, n, 16), minval=0.8, maxval=0.99)
    u = jax.random.normal(ks[1], (batch, n, 16))
    got = rglru(a, u, interpret=True)
    assert_kernel_close(got, rglru_ref(a, u), dtype, scale=10.0)


def _run_ssd_fused(dtype, batch, n):
    """Several chunks under a pinned config: the intra launch and the
    sequential state-and-apply launch must match the sequential reference
    on an odd/prime grid."""
    import jax

    from repro.kernels.ssd.ops import ssd
    from repro.kernels.ssd.ref import ssd_ref
    ks = jax.random.split(jax.random.PRNGKey(batch * 1000 + n), 4)
    x = jax.random.normal(ks[0], (batch, n, 2, 16))
    a = jax.random.uniform(ks[1], (batch, n, 2), minval=0.85, maxval=0.999)
    b = jax.random.normal(ks[2], (batch, n, 8)) * 0.3
    c = jax.random.normal(ks[3], (batch, n, 8)) * 0.3
    got = ssd(x, a, b, c, config={"tile_n": min(128, n)}, interpret=True,
              use_pallas=True)
    assert_kernel_close(got, ssd_ref(x, a, b, c), dtype, scale=10.0)


def _run_rglru_fused(dtype, batch, n):
    """The gate folded into the scan kernel's first stage, under a pinned
    config, must match the oracle."""
    import jax

    from repro.kernels.rglru.ops import rglru
    from repro.kernels.rglru.ref import rglru_ref
    ks = jax.random.split(jax.random.PRNGKey(batch * 1000 + n), 2)
    a = jax.random.uniform(ks[0], (batch, n, 16), minval=0.8, maxval=0.99)
    u = jax.random.normal(ks[1], (batch, n, 16))
    cfg = {"tile_n": min(128, n), "rows_per_program": 8, "radix": 2}
    got = rglru(a, u, config=cfg, interpret=True, use_pallas=True)
    assert_kernel_close(got, rglru_ref(a, u), dtype, scale=10.0)


def _run_prefix_sum_radix(radix):
    """Mixed-radix stage plans: the forced radix does NOT divide n, so the
    plan's ragged final stage (stage_radices) is on the execution path."""
    def run(dtype, batch, n):
        import jax.numpy as jnp

        from repro.kernels.scan.ops import prefix_sum
        from repro.kernels.scan.ref import scan_add_ref
        x = jnp.asarray(_rng(f"scanr{radix}x{batch}x{n}").normal(
            size=(batch, n)), getattr(jnp, dtype))
        got = prefix_sum(x, config={"radix": radix, "tile_n": n},
                         interpret=True, use_pallas=True)
        assert_kernel_close(got, scan_add_ref(x), dtype)
    return run


def _run_linrec_radix(radix):
    def run(dtype, batch, n):
        import jax.numpy as jnp

        from repro.kernels.scan.ops import linear_recurrence
        from repro.kernels.scan.ref import scan_linrec_assoc_ref
        rng = _rng(f"linrecr{radix}x{batch}x{n}")
        a = jnp.asarray(rng.uniform(0.8, 0.99, size=(batch, n)),
                        getattr(jnp, dtype))
        b = jnp.asarray(rng.normal(size=(batch, n)), getattr(jnp, dtype))
        got = linear_recurrence(a, b, config={"radix": radix, "tile_n": n},
                                interpret=True, use_pallas=True)
        assert_kernel_close(got, scan_linrec_assoc_ref(a, b), dtype)
    return run


def _run_fft_radix(radix):
    """Historically crashed at trace time (rr = min(radix, n_cur) stopped
    dividing n_cur); the plan's exact factorization must launch and match."""
    def run(dtype, batch, n):
        import jax.numpy as jnp

        from repro.kernels.fft.ops import fft
        from repro.kernels.fft.ref import fft_ref
        rng = _rng(f"fftr{radix}x{batch}x{n}")
        x = jnp.asarray(rng.normal(size=(batch, n))
                        + 1j * rng.normal(size=(batch, n)), jnp.complex64)
        got = fft(x, config={"radix": radix}, interpret=True)
        assert_kernel_close(got, fft_ref(x), dtype)
    return run


def _run_attention(dtype, batch, n):
    import jax
    import jax.numpy as jnp

    from repro.kernels.attention.ops import attention
    from repro.kernels.attention.ref import attention_ref
    ks = jax.random.split(jax.random.PRNGKey(batch * 1000 + n), 3)
    q = jax.random.normal(ks[0], (batch, n, 64), getattr(jnp, dtype))
    k = jax.random.normal(ks[1], (batch, n, 64), getattr(jnp, dtype))
    v = jax.random.normal(ks[2], (batch, n, 64), getattr(jnp, dtype))
    got = attention(q, k, v, causal=True, interpret=True, use_pallas=True)
    assert_kernel_close(got, attention_ref(q, k, v, causal=True), dtype,
                        scale=10.0)


# entry -> (runner, dtypes, shapes). Shapes default to the shared
# odd/prime table; ops with extra constraints narrow them here, visibly.
_KERNEL_TABLE = {
    "prefix_sum": (_run_prefix_sum, ("float32", "bfloat16"),
                   ODD_BATCH_SHAPES),
    "linear_recurrence": (_run_linear_recurrence, ("float32",),
                          ODD_BATCH_SHAPES),
    "solve_pcr": (_run_tridiag("pcr"), ("float32",), ODD_BATCH_SHAPES),
    "solve_cr": (_run_tridiag("cr"), ("float32",), ((3, 96), (5, 100))),
    "solve_lf": (_run_tridiag("lf"), ("float32",), ((7, 96),)),
    "solve_wm": (_run_tridiag("wm"), ("float32",), ((5, 96),)),
    "fft": (_run_fft, ("complex64",), ODD_BATCH_SHAPES),
    # mixed-radix stage plans: radix does not divide n (96 = 2^5*3,
    # 768 = 2^8*3), odd/prime batches — exercises the ragged final stage
    "prefix_sum_radix3": (_run_prefix_sum_radix(3), ("float32",),
                          ((7, 96), (3, 768))),
    "prefix_sum_radix8": (_run_prefix_sum_radix(8), ("float32",),
                          ((7, 96), (3, 768))),
    "linear_recurrence_radix8": (_run_linrec_radix(8), ("float32",),
                                 ((5, 96),)),
    "fft_radix3": (_run_fft_radix(3), ("complex64",), ((5, 96),)),
    "fft_radix8": (_run_fft_radix(8), ("complex64",), ((7, 96), (3, 768))),
    # matmul shapes: (batch*11) x 65 x n — every dim odd or prime-factored
    "matmul": (_run_matmul, ("float32", "bfloat16"), ((3, 96), (5, 128))),
    "ssd": (_run_ssd, ("float32",), ((3, 96),)),
    # pinned multi-chunk / in-kernel-gate configs vs the oracle: the ssd
    # shapes pick nc = 2 and nc = 3 chunks
    "ssd_fused": (_run_ssd_fused, ("float32",), ((3, 256), (5, 384))),
    "rglru": (_run_rglru, ("float32",), ((3, 96), (5, 128))),
    "rglru_fused": (_run_rglru_fused, ("float32",), ODD_BATCH_SHAPES),
    "attention": (_run_attention, ("float32",), ((3, 192), (5, 256))),
}

KERNEL_CASES = tuple(
    KernelCase(entry, dtype, batch, n, run)
    for entry, (run, dtypes, shapes) in sorted(_KERNEL_TABLE.items())
    for dtype in dtypes
    for batch, n in shapes
)


def kernel_ops_entries() -> Sequence[str]:
    """Entry names the table covers (asserted against the registry)."""
    return tuple(sorted(_KERNEL_TABLE))


def pytest_generate_tests(metafunc):
    if "kernel_case" in metafunc.fixturenames:
        metafunc.parametrize("kernel_case", KERNEL_CASES,
                             ids=[c.id for c in KERNEL_CASES])
