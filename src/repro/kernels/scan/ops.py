"""Tuned scan entry points (prefix sum + linear recurrence).

Every call resolves its configuration through the default
:class:`repro.tuning.TunerSession` — DB hit (offline-tuned), else the
memoized analytical model (online, zero evaluations) — the paper's
deployment flow, then builds the :class:`StagePlan` that fixes the staged
execution (mixed-radix stage sequence, grid, carry scratch).  The plan is
the same object the analytical model and the ML featurizer consume, so
what runs is what was modeled.  ``plan.kind == "multipass"`` routes
large-N workloads through the §IV-C three-kernel driver.

Shapes are normalized to (batch, n) rows; callers with higher-rank arrays
flatten leading dims.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from repro.core.space import Workload, fit_block, scan_space
from repro.kernels.blocks import driver
from repro.kernels.blocks.plan import plan_for
from repro.kernels.scan.kernel import scan_add_pallas, scan_linrec_pallas
from repro.kernels.scan.ref import scan_add_ref, scan_linrec_assoc_ref
from repro.tuning import default_session, plan_execution, tuned_kernel


def _normalize(cfg, wl, dims=None):
    """Fit tuned knobs to the (batch, n) launch geometry; project to the
    kwargs the scan kernels accept (``in_register`` is a space-only knob;
    linrec's fold order is fixed, so its ``unroll`` is dropped with the
    same variant-awareness its search space applies)."""
    out = {
        "rows_per_program": fit_block(cfg.get("rows_per_program", 8),
                                      max(wl.batch, 1)),
        "tile_n": fit_block(cfg.get("tile_n", wl.n), wl.n),
        "radix": cfg.get("radix", 2),
    }
    if wl.variant != "linrec" and wl.op != "rglru":
        out["unroll"] = cfg.get("unroll", 1)
    return out


def _plan_workload(wl, linrec: bool):
    """Workload the PLAN is built for: both entry points share op="scan"
    and accept any registered variant (DB keys stay caller-chosen), but
    the plan's plane accounting must follow the kernel that actually runs
    — linrec keeps three resident planes, prefix-sum two — so a legacy
    ``linear_recurrence(variant="ks")`` call still gets a linrec plan."""
    want = "linrec" if linrec else ("ks" if wl.variant == "linrec"
                                    else wl.variant)
    return wl if wl.variant == want else dataclasses.replace(wl, variant=want)


def _launch_name(plan_wl) -> str:
    """The kernels' name on a device trace, by the variant that runs:
    ``scan_ks``, ``scan_lf`` or ``scan_linrec`` (multi-pass launches add
    a stage suffix)."""
    return f"scan_{plan_wl.variant}"


@tuned_kernel("scan", space=scan_space, pallas=scan_add_pallas,
              reference=scan_add_ref, normalize=_normalize,
              variants=("ks", "lf", "linrec"))
def prefix_sum(x: jax.Array, variant: str = "ks",
               config: Optional[dict] = None,
               interpret: Optional[bool] = None,
               use_pallas: Optional[bool] = None) -> jax.Array:
    """Inclusive row-wise prefix sum with tuned blocking."""
    batch, n = x.shape
    use_pallas, interpret = plan_execution(use_pallas, interpret)
    if not use_pallas:
        return scan_add_ref(x)
    wl = Workload(op="scan", n=n, batch=batch, variant=variant)
    cfg = default_session().resolve(wl, config=config)
    plan_wl = _plan_workload(wl, linrec=False)
    plan = plan_for(plan_wl, cfg)
    name = _launch_name(plan_wl)
    if plan.kind == "multipass":
        return driver.multipass_scan_add(x, plan, unroll=cfg.get("unroll", 1),
                                         interpret=interpret, name=name)
    return driver.launch(scan_add_pallas, plan.launches[0], x,
                         rows_per_program=plan.rows, tile_n=plan.tile_n,
                         stages=plan.stages, unroll=cfg.get("unroll", 1),
                         interpret=interpret, name=name)


@tuned_kernel("scan", space=scan_space, pallas=scan_linrec_pallas,
              reference=scan_linrec_assoc_ref, normalize=_normalize,
              variants=("ks", "lf", "linrec"))
def linear_recurrence(a: jax.Array, b: jax.Array, variant: str = "linrec",
                      config: Optional[dict] = None,
                      interpret: Optional[bool] = None,
                      use_pallas: Optional[bool] = None) -> jax.Array:
    """h_t = a_t h_{t-1} + b_t row-wise with tuned blocking.

    The workhorse behind RG-LRU layers and SSD inter-chunk state scans.
    """
    batch, n = a.shape
    use_pallas, interpret = plan_execution(use_pallas, interpret)
    if not use_pallas:
        return scan_linrec_assoc_ref(a, b)
    wl = Workload(op="scan", n=n, batch=batch, variant=variant)
    cfg = default_session().resolve(wl, config=config)
    plan_wl = _plan_workload(wl, linrec=True)
    plan = plan_for(plan_wl, cfg)
    name = _launch_name(plan_wl)
    if plan.kind == "multipass":
        return driver.multipass_linrec(a, b, plan, interpret=interpret,
                                       name=name)
    return driver.launch(scan_linrec_pallas, plan.launches[0], a, b,
                         rows_per_program=plan.rows, tile_n=plan.tile_n,
                         stages=plan.stages, interpret=interpret, name=name)
