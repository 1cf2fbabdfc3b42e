"""Pallas TPU kernel: flash attention with tunable block sizes.

Online-softmax tiling (Dao et al., re-tiled for the MXU): grid
(batch*heads, Lq/block_q, Lk/block_k) with the key dimension sequential per
core; VMEM scratch carries the running max/denominator/accumulator. block_q
and block_k are the tuned parameters (op="attention" search space) — the
beyond-paper application of the paper's methodology to the framework's
hottest kernel.

Causal and local-window (RecurrentGemma) masks are computed from global
positions; with causal masking, fully-masked k-blocks are skipped via
pl.when (the occupancy analogue of not launching dead threadblocks).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks.primitives import compiler_params

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, lq: int, lk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # global positions; queries occupy the LAST lq slots of the kv stream
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + (lk - lq)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # a block is live unless its whole score tile is masked out
    live = jnp.bool_(True)
    if causal:
        live &= (ki * block_k) <= (qi * block_q + (lk - lq) + block_q - 1)
    if window is not None:
        live &= ((ki + 1) * block_k - 1) > (qi * block_q + (lk - lq) - window)

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "causal",
                                             "window", "scale", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           block_q: int = 256, block_k: int = 256,
                           causal: bool = True,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           interpret: bool = False) -> jax.Array:
    """q: (BH, Lq, D), k/v: (BH, Lk, D) -> (BH, Lq, D); ``scale`` is the
    softmax scale (None: 1/sqrt(D))."""
    BH, lq, d = q.shape
    lk = k.shape[1]
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    grid = (BH, lq // block_q, lk // block_k)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, lq=lq, lk=lk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, t: (i, t, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, t: (i, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j, t: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(q, k, v)
