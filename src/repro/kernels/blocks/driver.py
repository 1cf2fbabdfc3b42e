"""Generic staged-execution driver: runs a StagePlan's launch list.

Generalizes the FFT four-step decomposition (paper §IV-C) into a driver
any prefix-family kernel can use, so large-N scan — and through the scan,
tridiag substitution sweeps and RG-LRU — also get the
m-kernel multi-pass path instead of only FFT:

  * ``four_step_fft``     — N = n1*n2 column/row decomposition, recursing
                            through the plan's children (m = 2 or 3);
  * ``multipass_scan_add`` / ``multipass_linrec`` — the three-launch
                            block-scan decomposition (chunk scan, carry
                            scan over chunk transfer operators, apply);
  * ``linrec_rows``       — the tuned linear-recurrence building block as
                            a library call for composite kernels (the
                            tridiag LF sweeps), with the XLA
                            reference as fallback where the radix spaces
                            have no valid config (odd lengths).

Every pallas launch is announced to ``record_launch`` with the plan's
``Launch`` record; ``capture_launches`` lets the conformance tests assert
that what runs is exactly what the plan promised.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.space import Workload
from repro.kernels.blocks.plan import Launch, StagePlan
from repro.kernels.blocks.primitives import compiler_params

_TRACE = threading.local()


@contextlib.contextmanager
def capture_launches():
    """Collect every Launch executed in this thread under the context."""
    captured: List[Launch] = []
    prev = getattr(_TRACE, "sink", None)
    _TRACE.sink = captured
    try:
        yield captured
    finally:
        _TRACE.sink = prev


def record_launch(launch: Launch) -> None:
    sink = getattr(_TRACE, "sink", None)
    if sink is not None:
        sink.append(launch)


def launch(kernel_fn: Callable, record: Launch, *args, **kwargs):
    """Record ``record`` and invoke the (jitted) kernel wrapper."""
    record_launch(record)
    return kernel_fn(*args, **kwargs)


# ---------------------------------------------------------------------------
# Four-step FFT (plan-driven; moved here from kernels/fft/ops.py)
# ---------------------------------------------------------------------------

def _kernel_fft(x: jax.Array, plan: StagePlan, inverse: bool,
                interpret: bool) -> jax.Array:
    from repro.kernels.fft.kernel import fft_pallas
    re, im = jnp.real(x).astype(jnp.float32), jnp.imag(x).astype(jnp.float32)
    record_launch(plan.launches[0])
    yre, yim = fft_pallas(re, im, rows_per_program=plan.rows,
                          stages=plan.stages, inverse=inverse,
                          reorder=plan.launches[0].reorder,
                          interpret=interpret)
    return jax.lax.complex(yre, yim)


def dispatch_fft(x: jax.Array, plan: StagePlan, *, inverse: bool,
                 interpret: bool) -> jax.Array:
    """Run a (possibly multi-pass) FFT plan on complex (batch, n) rows."""
    if plan.kind == "fused":
        return _kernel_fft(x, plan, inverse, interpret)
    return four_step_fft(x, plan, inverse=inverse, interpret=interpret)


def four_step_fft(x: jax.Array, plan: StagePlan, *, inverse: bool,
                  interpret: bool) -> jax.Array:
    """Bailey four-step N = n1*n2: column FFTs, twiddle, row FFTs,
    transpose — the §IV-C m-kernel path, launch list == plan.launches."""
    col_plan, row_plan = plan.children
    batch, n = x.shape
    n1, n2 = row_plan.n, col_plan.n
    sign = 1.0 if inverse else -1.0
    v = x.reshape(batch, n2, n1)
    # kernel(s) 1: length-n2 FFTs down the columns (batch*n1 problems);
    # recurses when n2 itself exceeds the resident tile (m = 3, paper:
    # N >= 2^19 on the 48KB-tile device)
    vc = jnp.transpose(v, (0, 2, 1)).reshape(batch * n1, n2)
    vc = dispatch_fft(vc, col_plan, inverse=inverse, interpret=interpret)
    v = jnp.transpose(vc.reshape(batch, n1, n2), (0, 2, 1))
    # twiddle
    k2 = jnp.arange(n2).reshape(1, n2, 1)
    k1 = jnp.arange(n1).reshape(1, 1, n1)
    v = v * jnp.exp(sign * 2j * jnp.pi * (k1 * k2) / n).astype(jnp.complex64)
    # kernel 2: length-n1 FFTs along rows
    vr = dispatch_fft(v.reshape(batch * n2, n1), row_plan, inverse=inverse,
                      interpret=interpret)
    v = vr.reshape(batch, n2, n1)
    # transpose for self-sorting output
    return jnp.transpose(v, (0, 2, 1)).reshape(batch, n)


# ---------------------------------------------------------------------------
# Multi-pass block scan (three launches)
# ---------------------------------------------------------------------------

def _apply_add_kernel(y_ref, e_ref, o_ref):
    y = y_ref[...].astype(jnp.float32)
    e = e_ref[...].astype(jnp.float32)
    o_ref[...] = (y + e).astype(o_ref.dtype)


def _apply_linrec_kernel(h_ref, p_ref, e_ref, o_ref):
    h = h_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    e = e_ref[...].astype(jnp.float32)
    o_ref[...] = (h + p * e).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rows", "interpret", "name"))
def _apply_add(y, entry, *, rows: int, interpret: bool,
               name: Optional[str] = None):
    batch, n = y.shape
    grid = (batch // rows,)
    return pl.pallas_call(
        _apply_add_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, n), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
        name=name,
    )(y, entry)


@functools.partial(jax.jit, static_argnames=("rows", "interpret", "name"))
def _apply_linrec(h, prod, entry, *, rows: int, interpret: bool,
                  name: Optional[str] = None):
    batch, n = h.shape
    grid = (batch // rows,)
    row_spec = pl.BlockSpec((rows, n), lambda i: (i, 0))
    return pl.pallas_call(
        _apply_linrec_kernel,
        grid=grid,
        in_specs=[row_spec, row_spec, pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct(h.shape, h.dtype),
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
        name=name,
    )(h, prod, entry)


def _stage_name(name: Optional[str], stage: str) -> Optional[str]:
    """Launch name of one stage of a named multi-pass op."""
    return None if name is None else f"{name}_{stage}"


def multipass_scan_add(x: jax.Array, plan: StagePlan, *, unroll: int = 1,
                       interpret: bool = False,
                       name: Optional[str] = None) -> jax.Array:
    """Prefix sum over (batch, n) as three kernels: per-chunk scans,
    exclusive scan over chunk sums, entry broadcast — HBM roundtrips
    between launches instead of a serialized carry chain.  A ``name``
    names the launches ``<name>_chunk``, ``<name>_carry`` and
    ``<name>_apply``."""
    from repro.kernels.scan.kernel import scan_add_pallas
    l1, l2, l3 = plan.launches
    batch, n = x.shape
    p, length = plan.seq_tiles, plan.tile_n
    # inter-launch carries round-trip through HBM; sub-f32 dtypes compute
    # the whole pipeline in f32 and quantize ONCE at the output, matching
    # the fused path's f32 VMEM carry scratch (bf16 chunk sums at
    # magnitude ~sqrt(n) would otherwise quantize every entry offset)
    xc = x.reshape(batch * p, length)
    if x.dtype != jnp.float32:
        xc = xc.astype(jnp.float32)
    record_launch(l1)
    y_local = scan_add_pallas(xc, rows_per_program=l1.block_shape[0],
                              tile_n=length, stages=l1.stages, unroll=unroll,
                              interpret=interpret,
                              name=_stage_name(name, "chunk"))
    sums = y_local[:, -1].reshape(batch, p)
    record_launch(l2)
    # the carry scan's tile is the CHUNK COUNT p, not tile_n: the
    # workload-tuned unroll was fit to tile_n and can exceed p when the
    # plan was built with a small seq_limit — clamp to the l2 launch
    # record's own tile so the balanced-tree fold never outgrows it
    csums = scan_add_pallas(sums, rows_per_program=l2.block_shape[0],
                            tile_n=p, stages=l2.stages,
                            unroll=max(1, min(unroll, l2.block_shape[1])),
                            interpret=interpret,
                            name=_stage_name(name, "carry"))
    entry = jnp.pad(csums[:, :-1], ((0, 0), (1, 0))).reshape(batch * p, 1)
    record_launch(l3)
    y = _apply_add(y_local, entry, rows=l3.block_shape[0],
                   interpret=interpret, name=_stage_name(name, "apply"))
    return y.reshape(batch, n).astype(x.dtype)


def multipass_linrec(a: jax.Array, b: jax.Array, plan: StagePlan, *,
                     gate: bool = False,
                     interpret: bool = False,
                     name: Optional[str] = None) -> jax.Array:
    """h_t = a_t h_{t-1} + b_t as three kernels: per-chunk linrec (+ the
    chunk transfer operators), carry linrec over operators, apply.

    ``gate=True`` is the rglru path: ``b`` carries the raw input u
    and the chunk kernel applies the RG-LRU gate in-tile (the carry and
    apply launches operate on transfer operators, untouched by the gate).
    ``name`` names the launches as in ``multipass_scan_add``.
    """
    from repro.kernels.scan.kernel import (scan_linrec_pallas,
                                           scan_linrec_prod_pallas)
    l1, l2, l3 = plan.launches
    batch, n = a.shape
    p, length = plan.seq_tiles, plan.tile_n
    ac = a.reshape(batch * p, length)
    bc = b.reshape(batch * p, length)
    if a.dtype != jnp.float32:        # see multipass_scan_add: one-shot
        ac = ac.astype(jnp.float32)   # output quantization, f32 carries
        bc = bc.astype(jnp.float32)
    record_launch(l1)
    h_local, a_cum = scan_linrec_prod_pallas(
        ac, bc, rows_per_program=l1.block_shape[0], stages=l1.stages,
        gate=gate, interpret=interpret, name=_stage_name(name, "chunk"))
    # chunk transfer operator: state_out = A * state_in + B
    A = a_cum[:, -1].reshape(batch, p)
    B = h_local[:, -1].reshape(batch, p)
    record_launch(l2)
    exits = scan_linrec_pallas(A, B, rows_per_program=l2.block_shape[0],
                               tile_n=p, stages=l2.stages,
                               interpret=interpret,
                               name=_stage_name(name, "carry"))
    entry = jnp.pad(exits[:, :-1], ((0, 0), (1, 0))).reshape(batch * p, 1)
    record_launch(l3)
    h = _apply_linrec(h_local, a_cum, entry.astype(h_local.dtype),
                      rows=l3.block_shape[0], interpret=interpret,
                      name=_stage_name(name, "apply"))
    return h.reshape(batch, n).astype(a.dtype)


# ---------------------------------------------------------------------------
# Linear recurrence as a library building block
# ---------------------------------------------------------------------------

def _linrec_space_valid(n: int) -> bool:
    # the radix spaces have no valid config for odd lengths (pinned by
    # tests); composite kernels fall back to the XLA reference there
    return n >= 2 and n % 2 == 0


def linrec_rows(a: jax.Array, b: jax.Array, *, use_pallas: bool,
                interpret: bool) -> jax.Array:
    """Tuned linear recurrence over (rows, n) — the shared carry-chain
    block composite kernels (the tridiag LF sweeps) call.

    Resolves the (op="scan", variant="linrec") workload through the
    session, builds its StagePlan, and dispatches fused or multi-pass
    exactly like the public ``linear_recurrence`` entry point.
    """
    from repro.kernels.scan.ref import scan_linrec_assoc_ref
    rows, n = a.shape
    if n <= 1:
        return b
    if not (use_pallas and _linrec_space_valid(n)):
        return scan_linrec_assoc_ref(a, b)
    from repro.kernels.scan.kernel import scan_linrec_pallas
    from repro.kernels.blocks.plan import plan_for
    from repro.tuning import default_session
    wl = Workload(op="scan", n=n, batch=rows, variant="linrec")
    plan = plan_for(wl, default_session().resolve(wl))
    if plan.kind == "multipass":
        return multipass_linrec(a, b, plan, interpret=interpret)
    return launch(scan_linrec_pallas, plan.launches[0], a, b,
                  rows_per_program=plan.rows, tile_n=plan.tile_n,
                  stages=plan.stages, interpret=interpret)
