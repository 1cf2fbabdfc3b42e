"""Pallas TPU kernel: batched complex FFT (in-place DIF, radix-r).

Complex data is carried as split re/im f32 planes (TPU VREGs are real; the
paper's BPLG similarly multiplexes real/imaginary shared-memory planes for
large tiles, §V-C). Each grid program transforms `rows_per_program` whole
problems resident in VMEM.

The staged loop is driven by the plan's mixed-radix stage sequence
(``blocks.plan.stage_radices``): stage t applies the shared ``butterfly``
building block at that stage's fan-in, as lane shifts times per-lane
complex coefficients (``primitives.dif_coefficients``, built once per
(n, stages) on the host and held in VMEM).  A self-sorting Stockham stage
would repack digits across the lane dim, which Mosaic cannot lower; the
in-place DIF leaves its output in digit-reversed order instead, and the
launch reorders it in VMEM (``primitives.digit_reverse_rows``: transpose,
strided sublane gathers, transpose back) before it stores, so the launch
writes natural order.  Where n is not a power of two the wrapper reorders
it with one reshape/transpose in XLA (``plan.fft_reorder_site``).
Because the stage sequence factors n exactly, the ragged final stage is
just a smaller butterfly.

Tunables: rows_per_program, radix; tile_n = n (whole-problem residency);
multi-pass large-N handled by the four-step driver in blocks/driver.py.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import primitives as prim
from repro.kernels.blocks.plan import fft_reorder_site, stage_radices


def _fft_kernel(re_ref, im_ref, cre_ref, cim_ref, ore_ref, oim_ref,
                *scratch, offsets: Tuple[Tuple[int, ...], ...], scale: float,
                stages: Tuple[int, ...]):
    re = re_ref[...].astype(jnp.float32)
    im = im_ref[...].astype(jnp.float32)
    row = 0
    for stage_offsets in offsets:
        re, im = prim.butterfly(re, im, cre_ref, cim_ref, row, stage_offsets)
        row += len(stage_offsets)
    ore_ref[...] = (re * scale).astype(ore_ref.dtype)
    oim_ref[...] = (im * scale).astype(oim_ref.dtype)
    for tmp_ref in scratch:               # the reorder runs in the kernel
        prim.digit_reverse_rows(ore_ref, tmp_ref, stages)
        prim.digit_reverse_rows(oim_ref, tmp_ref, stages)


@functools.lru_cache(maxsize=64)
def _coefficients(n: int, stages: Tuple[int, ...], inverse: bool):
    return prim.dif_coefficients(n, stages, inverse)


@functools.partial(jax.jit, static_argnames=("rows_per_program", "radix",
                                             "stages", "inverse",
                                             "reorder", "interpret"))
def fft_pallas(re: jax.Array, im: jax.Array, *, rows_per_program: int = 4,
               radix: int = 2, stages: Optional[Tuple[int, ...]] = None,
               inverse: bool = False, reorder: Optional[str] = None,
               interpret: bool = False):
    """Row-wise complex FFT on split planes; returns (re, im) in natural
    order.  ``reorder`` says where the digit reversal runs, ``"kernel"``
    or ``"xla"`` (the plan's ``Launch.reorder``); by default
    ``fft_reorder_site(n)``."""
    batch, n = re.shape
    rows = rows_per_program
    stages = prim.as_stages(stages) if stages else stage_radices(n, radix)
    coef_re, coef_im, offsets = _coefficients(n, stages, inverse)
    spec = pl.BlockSpec((rows, n), lambda i: (i, 0))
    coef_spec = pl.BlockSpec(coef_re.shape, lambda i: (0, 0))
    in_kernel = (reorder or fft_reorder_site(n)) == "kernel"
    scratch = ([pltpu.VMEM((n, min(rows, prim.LANE_TILE)), re.dtype)]
               if in_kernel else [])
    kernel = functools.partial(_fft_kernel, offsets=offsets,
                               scale=(1.0 / n) if inverse else 1.0,
                               stages=stages)
    yre, yim = pl.pallas_call(
        kernel,
        grid=(batch // rows,),
        in_specs=[spec, spec, coef_spec, coef_spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(re.shape, re.dtype)] * 2,
        scratch_shapes=scratch,
        compiler_params=prim.compiler_params("parallel"),
        interpret=interpret,
    )(re, im, jnp.asarray(coef_re), jnp.asarray(coef_im))
    if in_kernel:
        return yre, yim
    return prim.digit_reverse(yre, stages), prim.digit_reverse(yim, stages)
