"""Pallas TPU kernel: batched Parallel Cyclic Reduction (PCR) tridiagonal solve.

Each grid program solves `rows_per_program` independent systems of size n
kept fully VMEM-resident (the paper's BPLG requirement that the problem fit
shared memory maps to the whole system fitting the VMEM block; each element
carries 4 coefficients, matching the paper's accounting).

PCR runs ceil(log2 n) full-width reduction steps; after the last step every
equation is decoupled: x_i = d_i / b_i. Shifted neighbour access is a
lane-dim `concatenate` with identity fill (b=1 so the pivots stay finite;
a/c/d fill 0 so out-of-range terms vanish).

Tunables: rows_per_program (DMA block height), unroll (fold grouping hint),
in_register (skip scratch; systems solved wholly in VREG tiles). PCR's radix
is fixed at 2 (paper §V-A: only WM admits radix retuning).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blocks import primitives as prim


def _pcr_kernel(a_ref, b_ref, c_ref, d_ref, x_ref, *, n: int, unroll: int):
    del unroll
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)

    steps = max(1, math.ceil(math.log2(n)))
    stride = 1
    for _ in range(steps):
        a, b, c, d = prim.pcr_step(a, b, c, d, stride)
        stride *= 2
    x_ref[...] = (d / b).astype(x_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rows_per_program", "unroll",
                                             "interpret"))
def pcr_pallas(a: jax.Array, b: jax.Array, c: jax.Array, d: jax.Array, *,
               rows_per_program: int = 8, unroll: int = 1,
               interpret: bool = False) -> jax.Array:
    batch, n = a.shape
    rows = rows_per_program
    grid = (batch // rows,)
    spec = pl.BlockSpec((rows, n), lambda i: (i, 0))
    kernel = functools.partial(_pcr_kernel, n=n, unroll=unroll)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * 4,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        compiler_params=prim.compiler_params("parallel"),
        interpret=interpret,
    )(a, b, c, d)
