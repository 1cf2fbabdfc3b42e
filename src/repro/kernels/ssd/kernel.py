"""Pallas TPU kernels for the Mamba-2 SSD chunked algorithm.

Two launches (Dao & Gu 2024, adapted to TPU tiling):
  intra (``ssd_intra_pallas``): per (batch*head, chunk) block, the
    intra-chunk output via the quadratic dual form — Q x Q attention-like
    matmuls that map straight onto the MXU — plus the chunk's (S, P) state
    injection;
  carry (``ssd_state_apply_pallas``): the inter-chunk recurrence and its
    apply in one launch whose chunk axis is sequential and whose (S, P)
    VMEM carry *is* the recurrence state — the intra launch's chunk states
    feed it without an HBM roundtrip of the scanned entry states.

Tunables: chunk length Q (the VMEM tile; tile_n in the tuning space) and
rows via the grid. Q is hardware-aligned to the 128-lane MXU edge.

Each launch carries a name, which becomes its HLO instruction name on a
device trace: ``ssd_chunk`` (intra) and ``ssd_carry`` (recurrence +
apply) — the stage names of the multi-pass scan driver, so the two read
as one kernel family ``ssd``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks.primitives import compiler_params

# f32 contractions at full precision: the MXU's default single bf16 pass
# loses ~3 digits against the f32 recurrence the op computes
_EXACT = jax.lax.Precision.HIGHEST


def chunk_log_decay(a: jax.Array, chunk: int) -> jax.Array:
    """Cumulative log decay within each chunk: (BH, L) -> (BH, L) f32.

    Computed once in XLA and handed to every kernel in two layouts — a
    column (BH, L, 1) and a row (BH, 1, L) — so each (Q,)-vector block has
    a second-minor dim that is a multiple of 8 or the full dim, as Mosaic's
    (8, 128) tiling requires, and no kernel needs a cumulative sum or a
    transpose of its own.
    """
    bh, length = a.shape
    la = jnp.log(jnp.maximum(a.astype(jnp.float32), 1e-30))
    return jnp.cumsum(la.reshape(bh, length // chunk, chunk),
                      axis=-1).reshape(bh, length)


def _intra_kernel(x_ref, lac_ref, lar_ref, b_ref, c_ref, y_ref, st_ref):
    x = x_ref[0].astype(jnp.float32)      # (Q, P)
    la_col = lac_ref[0]                   # (Q, 1) cumulative log decay
    la_row = lar_ref[0]                   # (1, Q) the same, as a row
    b = b_ref[0].astype(jnp.float32)      # (Q, S)
    c = c_ref[0].astype(jnp.float32)      # (Q, S)
    q = x.shape[0]

    diff = la_col - la_row                                     # (Q, Q) t,s
    mask = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    ratio = jnp.exp(jnp.where(mask, diff, -1e30))  # mask inside exp (no inf)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             precision=_EXACT,
                             preferred_element_type=jnp.float32)  # (Q, Q)
    scores = cb * ratio
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            precision=_EXACT,
                            preferred_element_type=jnp.float32)   # (Q, P)

    decay_end = jnp.exp(la_col[q - 1:, :] - la_col)            # (Q, 1)
    bw = b * decay_end                                         # (Q, S)
    state = jax.lax.dot_general(bw, x, (((0,), (0,)), ((), ())),
                                precision=_EXACT,
                                preferred_element_type=jnp.float32)  # (S, P)
    y_ref[0] = y.astype(y_ref.dtype)
    st_ref[0, 0] = state.astype(st_ref.dtype)


def _vector_specs(chunk: int):
    """Column and row BlockSpecs of the (Q,)-vector decay inputs."""
    return (pl.BlockSpec((1, chunk, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, chunk), lambda i, j: (i, 0, j)))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "name"))
def ssd_intra_pallas(x, la, b, c, *, chunk: int = 128,
                     interpret: bool = False, name: str = "ssd_chunk"):
    """x: (BH, L, P); la: (BH, L) chunk-cumulative log decay
    (``chunk_log_decay``); b, c: (BH, L, S) — b/c pre-broadcast.

    Returns (y_intra (BH, L, P), state (BH, nc, S, P)).
    """
    BH, L, P = x.shape
    S = b.shape[-1]
    nc = L // chunk
    col, row = _vector_specs(chunk)
    y, st = pl.pallas_call(
        _intra_kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, j: (i, j, 0)),
            col, row,
            pl.BlockSpec((1, chunk, S), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, S), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, S, P), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, P), x.dtype),
            jax.ShapeDtypeStruct((BH, nc, S, P), jnp.float32),
        ],
        compiler_params=compiler_params("parallel", "parallel"),
        interpret=interpret,
        name=name,
    )(x, la[:, :, None], la[:, None, :], b, c)
    return y, st


def _state_apply_kernel(y_ref, lac_ref, c_ref, ac_ref, st_ref, o_ref,
                        carry_ref):
    """Recurrence + apply: the (S, P) VMEM carry is the recurrence state.

    The chunk axis is the grid's sequential dimension, so the carry
    entering program (i, j) is exactly h_{j-1} = the scanned entry state
    for chunk j; the kernel applies it to the chunk's output and advances
    the recurrence h_j = a_chunk_j * h_{j-1} + state_j in VMEM.
    """
    @pl.when(pl.program_id(1) == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)
    ent = carry_ref[...]                     # (S, P) entry state, f32
    y = y_ref[0].astype(jnp.float32)         # (Q, P)
    la_col = lac_ref[0]                      # (Q, 1)
    c = c_ref[0].astype(jnp.float32)         # (Q, S)
    y_in = jax.lax.dot_general(c, ent, (((1,), (0,)), ((), ())),
                               precision=_EXACT,
                               preferred_element_type=jnp.float32)  # (Q, P)
    o_ref[0] = (y + y_in * jnp.exp(la_col)).astype(o_ref.dtype)
    ac = ac_ref[0, 0].astype(jnp.float32)    # (1, P) a_chunk row
    st = st_ref[0, 0].astype(jnp.float32)
    carry_ref[...] = ac * ent + st


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "name"))
def ssd_state_apply_pallas(y_intra, la, c, state, *, chunk: int = 128,
                           interpret: bool = False, name: str = "ssd_carry"):
    """Inter-chunk recurrence + apply in one launch.

    y_intra: (BH, L, P); la: (BH, L) chunk-cumulative log decay;
    c: (BH, L, S); state: (BH, nc, S, P) chunk state injections straight
    out of ``ssd_intra_pallas``.  The sequential carry walks any nc.
    The chunk transitions a_chunk = exp(la[chunk end]) enter as (1, P)
    rows, which Mosaic broadcasts over the state's sublanes.
    """
    BH, L, P = y_intra.shape
    S = c.shape[-1]
    nc = L // chunk
    col, _ = _vector_specs(chunk)
    a_chunk = jnp.broadcast_to(
        jnp.exp(la.reshape(BH, nc, chunk)[..., -1])[:, :, None, None],
        (BH, nc, 1, P))
    return pl.pallas_call(
        _state_apply_kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, j: (i, j, 0)),
            col,
            pl.BlockSpec((1, chunk, S), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, 1, P), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, S, P), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, L, P), y_intra.dtype),
        scratch_shapes=[pltpu.VMEM((S, P), jnp.float32)],
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name=name,
    )(y_intra, la[:, :, None], c, a_chunk, state)
