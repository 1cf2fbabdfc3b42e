"""RG-LRU via the tuned linear-recurrence scan kernel.

The gate computation lives in the model layer; this op runs the recurrence
h_t = a_t h_{t-1} + sqrt(1-a_t^2) u_t by flattening (B, L, D) into
(B*D, L) rows for the scan kernel — the direct integration of the paper's
tuned scan into RecurrentGemma. The rglru workload resolves through the
TunerSession under its own op name (the space is the linrec-pruned scan
space), builds its StagePlan, and dispatches fused or multi-pass through
the shared blocks driver, so per-op DB entries and ``overrides(rglru=...)``
apply.

The Pallas path hands the scan kernel the raw input u: the elementwise
gate sqrt(1-a^2) * u runs inside the kernel's first stage (``gate=True``),
so the gated operand never makes an HBM roundtrip of its own.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.space import Workload, linrec_space
from repro.kernels.blocks import driver
from repro.kernels.blocks.plan import plan_for
from repro.kernels.scan.kernel import scan_linrec_pallas
from repro.kernels.scan.ops import _normalize as _normalize_scan
from repro.kernels.scan.ops import linear_recurrence
from repro.kernels.scan.ref import scan_linrec_assoc_ref
from repro.tuning import default_session, plan_execution, tuned_kernel


@tuned_kernel("rglru", space=linrec_space, pallas=scan_linrec_pallas,
              reference=scan_linrec_assoc_ref, normalize=_normalize_scan)
def rglru(a: jax.Array, u: jax.Array, config: Optional[dict] = None,
          interpret: Optional[bool] = None,
          use_pallas: Optional[bool] = None) -> jax.Array:
    B, L, D = a.shape
    run_pallas, interpret_eff = plan_execution(use_pallas, interpret)
    if not run_pallas:
        b = jnp.sqrt(jnp.maximum(1.0 - a * a, 0.0)) * u
        a_rows = jnp.transpose(a, (0, 2, 1)).reshape(B * D, L)
        b_rows = jnp.transpose(b, (0, 2, 1)).reshape(B * D, L)
        h = linear_recurrence(a_rows, b_rows, use_pallas=False)
        return jnp.transpose(h.reshape(B, D, L), (0, 2, 1))

    wl = Workload(op="rglru", n=L, batch=B * D)
    cfg = default_session().resolve(wl, config=config)
    plan = plan_for(wl, cfg)
    a_rows = jnp.transpose(a, (0, 2, 1)).reshape(B * D, L)
    u_rows = jnp.transpose(u, (0, 2, 1)).reshape(B * D, L)
    if plan.kind == "multipass":
        h = driver.multipass_linrec(a_rows, u_rows, plan, gate=True,
                                    interpret=interpret_eff)
    else:
        h = driver.launch(scan_linrec_pallas, plan.launches[0],
                          a_rows, u_rows, rows_per_program=plan.rows,
                          tile_n=plan.tile_n, stages=plan.stages,
                          gate=True, interpret=interpret_eff)
    return jnp.transpose(h.reshape(B, D, L), (0, 2, 1))
