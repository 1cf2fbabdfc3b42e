"""Pallas TPU kernel: batched radix-r prefix scan (add + linear-recurrence).

Layout: problems are rows of a (batch, n) array. The grid is
(batch/rows_per_program, n/tile_n); the column dimension is sequential on a
TPU core, so a VMEM scratch carries the running prefix across column tiles
(one streaming HBM pass; the parallel §IV-C multi-pass alternative lives in
``repro.kernels.blocks.driver``).

The in-block circuit is built from the shared building blocks
(``repro.kernels.blocks.primitives``): one ``shift_fold`` /
``linrec_level`` per stage of the plan's mixed-radix stage sequence
(``stage_radices`` — the paper's rule-4 radix lever, ragged final stage
included), plus the ``carry_*`` chain primitives across column tiles.

Tunable parameters consumed from the TuningDB config:
  tile_n, rows_per_program, radix, unroll (balanced-tree fold grouping;
  linrec's fold order is fixed by the algebra, so its space prunes it),
  in_register (space/model-only knob).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import primitives as prim
from repro.kernels.blocks.plan import stage_radices, stage_strides


def _scan_add_kernel(x_ref, o_ref, carry_ref, *, stages: Tuple[int, ...],
                     unroll: int, multi_tile: bool):
    if multi_tile:
        prim.carry_init(carry_ref)
    x = x_ref[...].astype(jnp.float32)
    for fan_in, stride in zip(stages, stage_strides(stages)):
        x = prim.shift_fold(x, fan_in, stride, fill=0.0, unroll=unroll)
    if multi_tile:
        x = prim.carry_fold_add(x, carry_ref)
    o_ref[...] = x.astype(o_ref.dtype)


def _scan_linrec_kernel(a_ref, b_ref, h_ref, carry_ref, *,
                        stages: Tuple[int, ...], multi_tile: bool,
                        gate: bool = False,
                        want_products: bool = False, p_ref=None):
    if multi_tile:
        prim.carry_init(carry_ref)
    aa = a_ref[...].astype(jnp.float32)
    bb = b_ref[...].astype(jnp.float32)
    if gate:
        # rglru: b_ref holds u; the elementwise gate runs as
        # the stage loop's prologue instead of a separate XLA HBM pass
        bb = prim.rglru_gate(aa, bb)
    for fan_in, stride in zip(stages, stage_strides(stages)):
        aa, bb = prim.linrec_level(aa, bb, fan_in, stride)
    # aa now holds prefix products of a; bb the zero-state response
    if want_products:
        p_ref[...] = aa.astype(p_ref.dtype)
    if multi_tile:
        h = prim.carry_fold_linrec(aa, bb, carry_ref)
    else:
        h = bb
    h_ref[...] = h.astype(h_ref.dtype)


def _linrec_prod_kernel(a_ref, b_ref, h_ref, p_ref, carry_ref, *,
                        stages: Tuple[int, ...], multi_tile: bool,
                        gate: bool = False):
    _scan_linrec_kernel(a_ref, b_ref, h_ref, carry_ref, stages=stages,
                        multi_tile=multi_tile, gate=gate, want_products=True,
                        p_ref=p_ref)


def _grid_and_specs(batch: int, n: int, rows: int, tile_n: int, n_in: int):
    grid = (batch // rows, n // tile_n)
    in_spec = pl.BlockSpec((rows, tile_n), lambda i, j: (i, j))
    out_spec = pl.BlockSpec((rows, tile_n), lambda i, j: (i, j))
    scratch = [pltpu.VMEM((rows, 1), jnp.float32)]
    return grid, [in_spec] * n_in, out_spec, scratch


def _resolve_stages(stages: Optional[Tuple[int, ...]], tile_n: int,
                    radix: int) -> Tuple[int, ...]:
    """Plans pass their stage sequence; direct callers fall back to the
    same decomposition the planner would produce."""
    return prim.as_stages(stages) if stages else stage_radices(tile_n, radix)


@functools.partial(jax.jit, static_argnames=("rows_per_program", "tile_n",
                                             "radix", "unroll", "stages",
                                             "interpret", "name"))
def scan_add_pallas(x: jax.Array, *, rows_per_program: int = 8,
                    tile_n: int = 0, radix: int = 2, unroll: int = 1,
                    stages: Optional[Tuple[int, ...]] = None,
                    interpret: bool = False,
                    name: Optional[str] = None) -> jax.Array:
    """Inclusive prefix sum over the last axis of (batch, n).

    ``name`` names the launch: it becomes the kernel's HLO instruction
    name, which a device trace shows (unnamed, the instruction takes
    this function's name)."""
    batch, n = x.shape
    tile_n = tile_n or n
    grid, in_specs, out_spec, scratch = _grid_and_specs(
        batch, n, rows_per_program, tile_n, 1)
    kernel = functools.partial(
        _scan_add_kernel, stages=_resolve_stages(stages, tile_n, radix),
        unroll=unroll, multi_tile=True)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=scratch,
        compiler_params=prim.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name=name,
    )(x)


@functools.partial(jax.jit, static_argnames=("rows_per_program", "tile_n",
                                             "radix", "unroll", "stages",
                                             "gate", "interpret", "name"))
def scan_linrec_pallas(a: jax.Array, b: jax.Array, *, rows_per_program: int = 8,
                       tile_n: int = 0, radix: int = 2, unroll: int = 1,
                       stages: Optional[Tuple[int, ...]] = None,
                       gate: bool = False,
                       interpret: bool = False,
                       name: Optional[str] = None) -> jax.Array:
    """h_t = a_t * h_{t-1} + b_t along the last axis of (batch, n) pairs.

    ``gate=True`` is the rglru path: ``b`` carries the raw input ``u``
    and the kernel applies the RG-LRU gate in-tile before the stage loop
    (one launch for the gate and the recurrence).  ``name``
    names the launch as in ``scan_add_pallas``.
    """
    del unroll  # fold order fixed by composition order for linrec
    batch, n = a.shape
    tile_n = tile_n or n
    grid, in_specs, out_spec, scratch = _grid_and_specs(
        batch, n, rows_per_program, tile_n, 2)
    kernel = functools.partial(
        _scan_linrec_kernel, stages=_resolve_stages(stages, tile_n, radix),
        multi_tile=True, gate=gate)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        scratch_shapes=scratch,
        compiler_params=prim.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name=name,
    )(a, b)


@functools.partial(jax.jit, static_argnames=("rows_per_program", "radix",
                                             "stages", "gate", "interpret",
                                             "name"))
def scan_linrec_prod_pallas(a: jax.Array, b: jax.Array, *,
                            rows_per_program: int = 8, radix: int = 2,
                            stages: Optional[Tuple[int, ...]] = None,
                            gate: bool = False,
                            interpret: bool = False,
                            name: Optional[str] = None):
    """Single-tile linrec returning (h, prefix products of a).

    The multi-pass driver's chunk kernel: each program holds whole rows
    (tile_n == n), so no carry chain — the products output is exactly the
    per-chunk transfer operator the carry scan then composes.  ``gate``
    fuses the RG-LRU input gate exactly as in ``scan_linrec_pallas``.
    """
    batch, n = a.shape
    rows = rows_per_program
    grid = (batch // rows, 1)
    spec = pl.BlockSpec((rows, n), lambda i, j: (i, j))
    kernel = functools.partial(
        _linrec_prod_kernel, stages=_resolve_stages(stages, n, radix),
        multi_tile=False, gate=gate)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32)],
        compiler_params=prim.compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name=name,
    )(a, b)
