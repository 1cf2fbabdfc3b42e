"""Pallas TPU kernels: tiled matmul, and the grouped matmul of the MoE
expert share.

The tiled matmul (tunable block_m, block_n, block_k) is the demonstration
target for applying the paper's tuning methodologies to an MXU-bound
kernel (the prefix ops are VPU/DMA-bound). K is the sequential grid
dimension; partial products accumulate in an f32 VMEM scratch.

The grouped matmul (``gmm_pallas``) multiplies each tile of rows by the
weights of the group (expert) the tile belongs to, the group of each tile
prefetched as a scalar and read by the index maps.
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


# ---------------------------------------------------------------------------
# Grouped matmul (the MoE expert share)
# ---------------------------------------------------------------------------

def _gmm_kernel(groups_ref, tiles_ref, x_ref, w_ref, o_ref, *, activation):
    del groups_ref                       # read by the index maps only

    @pl.when(pl.program_id(1) < tiles_ref[0])
    def _tile():
        y = jax.lax.dot_general(x_ref[...], w_ref[0],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if activation == "relu2":
            y = jnp.square(jnp.maximum(y, 0.0))
        o_ref[...] = y.astype(o_ref.dtype)


def _used_tile(i, tiles_ref):
    """Row tile ``i``, or the last tile in use once ``i`` is past them: a
    block index that does not change moves no data."""
    return jnp.maximum(jnp.minimum(i, tiles_ref[0] - 1), 0)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "activation", "interpret",
                                             "name"))
def gmm_pallas(x, w, tile_groups, tiles, *, block_m: int, block_n: int,
               activation=None, interpret: bool = False,
               name: str = "moe_gmm"):
    """Grouped matmul: row tile t of ``x`` times ``w[tile_groups[t]]``.

    x: (M, K), its rows grouped, each group padded to whole tiles of
    ``block_m`` rows; w: (E, K, N); tile_groups: (M // block_m,) int32,
    the group of each row tile; tiles: (1,) int32, the row tiles in use.
    Tiles past ``tiles`` are neither read, computed nor written: their
    output rows hold whatever the buffer held.  K is one block, so the
    row tiles of one group reuse the group's weight block; ``activation``
    ("relu2" or None) is applied to each product tile before it is
    stored.  Returns (M, N) in x's dtype.
    """
    m, k = x.shape
    n = w.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // block_n, m // block_m),
        in_specs=[
            pl.BlockSpec((block_m, k),
                         lambda j, i, g, t: (_used_tile(i, t), 0)),
            pl.BlockSpec((1, k, block_n),
                         lambda j, i, g, t: (g[_used_tile(i, t)], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda j, i, g, t: (_used_tile(i, t), j)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, activation=activation),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name=name,
    )(tile_groups, tiles, x, w)
