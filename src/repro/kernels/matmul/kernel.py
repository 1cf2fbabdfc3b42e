"""Pallas TPU kernel: tiled matmul with tunable (block_m, block_n, block_k).

The demonstration target for applying the paper's tuning methodologies to an
MXU-bound kernel (the prefix ops are VPU/DMA-bound). K is the sequential
grid dimension; partial products accumulate in an f32 VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks.primitives import compiler_params


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def matmul_pallas(a: jax.Array, b: jax.Array, *, block_m: int = 256,
                  block_n: int = 256, block_k: int = 256,
                  interpret: bool = False) -> jax.Array:
    m, k = a.shape
    _, n = b.shape
    block_m, block_n, block_k = min(block_m, m), min(block_n, n), min(block_k, k)
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, t: (i, t)),
            pl.BlockSpec((block_k, block_n), lambda i, j, t: (t, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(a, b)
