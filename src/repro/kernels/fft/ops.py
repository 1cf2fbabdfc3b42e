"""Tuned FFT entry points: in-VMEM Stockham + four-step large-N driver.

`fft(x)` — x complex (batch, n):
  * n <= max in-VMEM tile: single Stockham kernel launch, radix/rows from
    the TunerSession (paper §V-C small/medium sizes);
  * larger n: the op="large_fft" workload resolves through the same
    session and its StagePlan describes the Bailey four-step decomposition
    N = n1*n2 — executed by ``repro.kernels.blocks.driver.four_step_fft``
    (the paper's §IV-C multi-kernel strategy with m kernels; the tile
    split n1 comes from the tuned `tile_n`).
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.core.space import Workload, fft_space, large_fft_space
from repro.core.multikernel import max_resident_tile
from repro.kernels.blocks import driver
from repro.kernels.blocks.plan import plan_for
from repro.kernels.fft.kernel import fft_pallas
from repro.kernels.fft.ref import fft_ref
from repro.tuning import default_session, plan_execution, tuned_kernel


def _normalize(cfg, wl, dims=None):
    """Raw Stockham knobs; rows are re-fitted per sub-launch (the four-step
    path runs the kernel at several different sub-batch sizes)."""
    return {"radix": cfg.get("radix", 2),
            "rows_per_program": cfg.get("rows_per_program", 4),
            "tile_n": cfg.get("tile_n", 2048)}


@tuned_kernel("fft", space=fft_space, pallas=fft_pallas, reference=fft_ref,
              normalize=_normalize, variants=("stockham",))
def fft(x: jax.Array, config: Optional[dict] = None,
        interpret: Optional[bool] = None, inverse: bool = False) -> jax.Array:
    batch, n = x.shape
    _, interpret = plan_execution(True, interpret)
    session = default_session()
    wl_small = Workload(op="fft", n=n, batch=batch, variant="stockham")
    max_tile = max_resident_tile(wl_small)
    if n <= max_tile:
        cfg = session.resolve(wl_small, config=config)
        plan = plan_for(wl_small, cfg)
        return driver.dispatch_fft(x, plan, inverse=inverse,
                                   interpret=interpret)

    # ---- four-step multi-kernel path (plan-driven) ----
    wl = Workload(op="large_fft", n=n, batch=batch, variant="stockham")
    cfg = session.resolve(wl, config=config)
    plan = plan_for(wl, cfg, max_tile=max_tile)
    return driver.four_step_fft(x, plan, inverse=inverse, interpret=interpret)


# the four-step driver resolves op="large_fft" through the same session;
# register its space under that name too
tuned_kernel("large_fft", space=large_fft_space, pallas=fft_pallas,
             reference=fft_ref, normalize=_normalize,
             variants=("stockham",))(fft)


def ifft(x: jax.Array, config: Optional[dict] = None,
         interpret: Optional[bool] = None) -> jax.Array:
    return fft(x, config=config, interpret=interpret, inverse=True)
