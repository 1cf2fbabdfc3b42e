"""Tridiagonal solver library: PCR (Pallas), CR, LF, WM (+ Thomas baseline).

The four parallel variants mirror the BPLG solver family (paper §III):
  pcr — Parallel Cyclic Reduction, full-width log2(n) steps (Pallas kernel);
  cr  — Cyclic Reduction, forward halving + back substitution;
  lf  — Ladner-Fischer: the LU-elimination recurrences recast as parallel
        prefixes (2x2 Mobius matrices for the pivots — the paper's "each
        element is composed of two equations" — plus two linear-recurrence
        scans for the substitution sweeps);
  wm  — Wang&Mou divide-and-conquer: the same prefix math evaluated chunk-
        wise (sequential inside a chunk of `radix * 16` elements, parallel
        across chunks) — the radix is the tunable fan-in, as in the paper.

`solve(..., variant=...)` resolves the configuration for the
(op="tridiag", variant, n, batch) workload through the TunerSession.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.space import Workload, fit_block, tridiag_space
from repro.kernels.blocks import driver
from repro.kernels.blocks.plan import plan_for, wm_chunk
from repro.kernels.tridiag.kernel import pcr_pallas
from repro.kernels.tridiag.ref import thomas_ref
from repro.tuning import default_session, plan_execution, tuned_kernel

# systems longer than this route the LF substitution sweeps through the
# multi-pass scan driver (paper §IV-C m-kernel path for tridiag)
LF_MULTIPASS_MIN = 1 << 15


def _normalize(cfg, wl, dims=None):
    """Variant-aware projection onto the knobs each solver actually
    consumes, so the resolved config uniquely determines the executed
    kernel (what the TuningDB records is what ran):

      pcr         -> rows_per_program, unroll;
      wm          -> radix plus the DERIVED chunk (the dispatch-time
                     ``radix * 16`` clamp moved here, single-sourced in
                     ``blocks.plan.wm_chunk``);
      cr/lf/thomas -> no knobs (their spaces are singletons).
    """
    if wl.variant == "wm":
        radix = cfg.get("radix", 2)
        return {"radix": radix, "chunk": wm_chunk(radix, wl.n)}
    if wl.variant in ("cr", "lf", "thomas"):
        return {}
    return {"rows_per_program": fit_block(cfg.get("rows_per_program", 8),
                                          max(wl.batch, 1)),
            "unroll": cfg.get("unroll", 1)}


# ---------------------------------------------------------------------------
# CR — cyclic reduction
# ---------------------------------------------------------------------------

@functools.partial(jax.jit)
def cr_solve(a, b, c, d):
    batch, n = a.shape
    levels = []
    while a.shape[-1] > 2:
        am, bm, cm, dm = (jnp.pad(v[..., :-1], ((0, 0), (1, 0)))
                          for v in (a, b, c, d))
        bm = bm.at[..., 0].set(1.0)
        ap, bp, cp, dp = (jnp.pad(v[..., 1:], ((0, 0), (0, 1)))
                          for v in (a, b, c, d))
        bp = bp.at[..., -1].set(1.0)
        alpha = -a / bm
        gamma = -c / bp
        a2 = alpha * am
        b2 = b + alpha * cm + gamma * ap
        c2 = gamma * cp
        d2 = d + alpha * dm + gamma * dp
        levels.append((a, b, c, d))
        a, b, c, d = (v[..., 1::2] for v in (a2, b2, c2, d2))
    # solve the 2x2 (or 1x1) core directly
    if a.shape[-1] == 1:
        x = d / b
    else:
        det = b[..., 0] * b[..., 1] - c[..., 0] * a[..., 1]
        x0 = (d[..., 0] * b[..., 1] - c[..., 0] * d[..., 1]) / det
        x1 = (b[..., 0] * d[..., 1] - d[..., 0] * a[..., 1]) / det
        x = jnp.stack([x0, x1], axis=-1)
    # back substitution
    for (a0, b0, c0, d0) in reversed(levels):
        xfull = jnp.zeros(a0.shape, a0.dtype)
        xfull = xfull.at[..., 1::2].set(x)
        xm = jnp.pad(xfull[..., :-1], ((0, 0), (1, 0)))
        xp = jnp.pad(xfull[..., 1:], ((0, 0), (0, 1)))
        xeven = (d0 - a0 * xm - c0 * xp) / b0
        xfull = xfull.at[..., 0::2].set(xeven[..., 0::2])
        x = xfull
    return x


# ---------------------------------------------------------------------------
# LF — parallel-prefix formulation
# ---------------------------------------------------------------------------

def _pivot_prefix(a, b, c):
    """LU pivots e_i via normalized 2x2 Mobius-matrix prefix products."""
    cm = jnp.pad(c[..., :-1], ((0, 0), (1, 0)))
    m00 = b
    m01 = -a * cm
    m10 = jnp.ones_like(b)
    m11 = jnp.zeros_like(b)
    # first matrix encodes e_0 = b_0 directly: [b0, 0; 1, 0] works since
    # v_{-1} = [1, 0]^T  ->  v_0 = [b0, 1]^T (after the ratio, e_0 = b0).
    m01 = m01.at[..., 0].set(0.0)

    def combine(x, y):
        # y (newer) @ x (older), normalized for scale stability
        y00, y01, y10, y11 = y
        x00, x01, x10, x11 = x
        z00 = y00 * x00 + y01 * x10
        z01 = y00 * x01 + y01 * x11
        z10 = y10 * x00 + y11 * x10
        z11 = y10 * x01 + y11 * x11
        s = jnp.maximum(jnp.maximum(jnp.abs(z00), jnp.abs(z01)),
                        jnp.maximum(jnp.abs(z10), jnp.abs(z11))) + 1e-30
        return z00 / s, z01 / s, z10 / s, z11 / s

    p00, p01, p10, p11 = jax.lax.associative_scan(
        combine, (m00, m01, m10, m11), axis=-1)
    # v_i = P_i [1, 0]^T = [p00, p10]
    return p00 / p10


def _linrec(a, b, reverse=False):
    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    if reverse:
        a = jnp.flip(a, -1)
        b = jnp.flip(b, -1)
    _, h = jax.lax.associative_scan(combine, (a, b), axis=-1)
    return jnp.flip(h, -1) if reverse else h


@functools.partial(jax.jit)
def lf_solve(a, b, c, d):
    e = _pivot_prefix(a, b, c)
    em = jnp.pad(e[..., :-1], ((0, 0), (1, 0)), constant_values=1.0)
    alpha = -a / em
    alpha = alpha.at[..., 0].set(0.0)
    y = _linrec(alpha, d)                      # forward substitution
    x = _linrec(-c / e, y / e, reverse=True)   # back substitution
    return x


def lf_solve_multipass(a, b, c, d, *, use_pallas: bool = True,
                       interpret: bool = False):
    """LF with the substitution sweeps on the multi-pass scan driver.

    The pivot prefix stays the normalized 2x2 scan (scale stability), but
    the forward/back linear recurrences run as the shared carry-chain
    building block — pallas-fused for small n, the §IV-C three-kernel
    decomposition once the row exceeds the resident tile.
    """
    e = _pivot_prefix(a, b, c)
    em = jnp.pad(e[..., :-1], ((0, 0), (1, 0)), constant_values=1.0)
    alpha = (-a / em).at[..., 0].set(0.0)
    y = driver.linrec_rows(alpha, d, use_pallas=use_pallas,
                           interpret=interpret)
    x = driver.linrec_rows(jnp.flip(-c / e, -1), jnp.flip(y / e, -1),
                           use_pallas=use_pallas, interpret=interpret)
    return jnp.flip(x, -1)


# ---------------------------------------------------------------------------
# WM — divide-and-conquer (chunked prefix)
# ---------------------------------------------------------------------------

def _chunked_linrec(a, b, chunk: int, reverse=False):
    """linrec via sequential scan inside chunks + associative scan across."""
    if reverse:
        a = jnp.flip(a, -1)
        b = jnp.flip(b, -1)
    batch, n = a.shape
    p = n // chunk
    ar = a.reshape(batch, p, chunk)
    br = b.reshape(batch, p, chunk)

    def step(carry, ab):
        ai, bi = ab
        h = ai * carry + bi
        return h, h

    # within-chunk, with zero entry state: gives local response + local
    # cumulative products
    _, hT = jax.lax.scan(step, jnp.zeros((batch, p), a.dtype),
                         (jnp.moveaxis(ar, -1, 0), jnp.moveaxis(br, -1, 0)))
    h_local = jnp.moveaxis(hT, 0, -1)                     # (batch, p, chunk)
    a_cum = jnp.cumprod(ar, axis=-1)
    # chunk transfer: state_out = A_chunk * state_in + B_chunk
    A_chunk = a_cum[..., -1]
    B_chunk = h_local[..., -1]

    def combine(l, r):
        al, bl = l
        ar_, br_ = r
        return al * ar_, ar_ * bl + br_

    _, carry_in = jax.lax.associative_scan(combine, (A_chunk, B_chunk), axis=-1)
    # entry state of chunk k = exit state of chunk k-1
    entry = jnp.pad(carry_in[..., :-1], ((0, 0), (1, 0)))
    h = h_local + a_cum * entry[..., None]
    h = h.reshape(batch, n)
    return jnp.flip(h, -1) if reverse else h


def wm_solve(a, b, c, d, chunk: int = 32):
    e = _pivot_prefix(a, b, c)   # pivots via tree prefix (shared)
    em = jnp.pad(e[..., :-1], ((0, 0), (1, 0)), constant_values=1.0)
    alpha = (-a / em).at[..., 0].set(0.0)
    y = _chunked_linrec(alpha, d, chunk)
    x = _chunked_linrec(-c / e, y / e, chunk, reverse=True)
    return x


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

@tuned_kernel("tridiag", space=tridiag_space, pallas=pcr_pallas,
              reference=thomas_ref, normalize=_normalize,
              variants=("pcr", "cr", "lf", "wm", "thomas"))
def solve(a, b, c, d, variant: str = "pcr", config: Optional[dict] = None,
          interpret: Optional[bool] = None):
    """Tuned batched tridiagonal solve; x with A x = d."""
    batch, n = a.shape

    def cfg():
        return default_session().resolve(
            Workload(op="tridiag", n=n, batch=batch, variant=variant),
            config=config)

    if variant == "pcr":
        _, interpret = plan_execution(True, interpret)
        c_ = cfg()
        plan = plan_for(Workload(op="tridiag", n=n, batch=batch,
                                 variant="pcr"), c_)
        return driver.launch(
            pcr_pallas, plan.launches[0], a, b, c, d,
            rows_per_program=c_["rows_per_program"], unroll=c_["unroll"],
            interpret=interpret)
    if variant == "cr":
        return cr_solve(a, b, c, d)
    if variant == "lf":
        if n > LF_MULTIPASS_MIN:
            use_pallas, interpret = plan_execution(None, interpret)
            return lf_solve_multipass(a, b, c, d, use_pallas=use_pallas,
                                      interpret=interpret)
        return lf_solve(a, b, c, d)
    if variant == "wm":
        return wm_solve(a, b, c, d, chunk=cfg()["chunk"])
    if variant == "thomas":
        return thomas_ref(a, b, c, d)
    raise ValueError(f"unknown tridiag variant {variant!r}")
