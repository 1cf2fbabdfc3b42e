"""Shared in-kernel building blocks (the BPLG CTA-primitive layer).

Every staged kernel in the repo is a composition of four primitives, all
operating on the trailing (lane) dimension of VMEM-resident tiles:

  * ``shift_fold``   — one radix-r Kogge-Stone level for an associative
                       monoid (prefix sum), with balanced-tree unrolling;
  * ``linrec_level`` — the same level for the (a, b) linear-recurrence
                       monoid (composition order fixed by the algebra);
  * ``butterfly``    — the radix-r complex DFT fold + twiddles of one
                       in-place DIF stage, as lane shifts times per-lane
                       coefficients (``dif_coefficients``), and
                       ``digit_reverse_rows``, the layout shuffle that
                       puts its output in natural order in VMEM;
  * ``carry chain``  — init/fold/store of the cross-tile VMEM carry that
                       turns a column-tiled grid into one streaming pass.

Extracted from the historical per-kernel copies in scan/fft/tridiag so a
new kernel family composes them instead of re-rolling its own stage loop
(docs/kernels.md walks through a port).  Stage sequences come from
``repro.kernels.blocks.plan.stage_radices`` — never recompute them here.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.hw.profiles import active_profile


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    """Mosaic parameters of every kernel launch: the grid's dimension
    semantics and, explicitly, the scoped-VMEM limit the planner bounds
    each launch by (``HardwareProfile.vmem_budget``) — so a config the
    plan admits is one the compiler accepts."""
    return pltpu.CompilerParams(
        dimension_semantics=dimension_semantics,
        vmem_limit_bytes=active_profile().vmem_budget)


# ---------------------------------------------------------------------------
# Lane shifts
# ---------------------------------------------------------------------------

def shift_lanes(x: jax.Array, off: int, fill: float) -> jax.Array:
    """Shift the trailing dim by ``off`` lanes, filling with the monoid
    identity.  off > 0 shifts right (element i sees neighbour i - off),
    off < 0 shifts left.  Mosaic lowers the concatenate to lane shifts."""
    if off == 0:
        return x
    pad = jnp.full(x.shape[:-1] + (abs(off),), fill, dtype=x.dtype)
    if off > 0:
        return jnp.concatenate([pad, x[..., :-off]], axis=-1)
    return jnp.concatenate([x[..., -off:], pad], axis=-1)


# ---------------------------------------------------------------------------
# Radix-r Kogge-Stone fold (associative monoid)
# ---------------------------------------------------------------------------

def _tree_fold(parts: List[jax.Array]) -> jax.Array:
    """Balanced pairwise reduction — associativity buys ILP (rule 3)."""
    while len(parts) > 1:
        nxt = []
        for i in range(0, len(parts) - 1, 2):
            nxt.append(parts[i] + parts[i + 1])
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    return parts[0]


def shift_fold(x: jax.Array, fan_in: int, stride: int, *, fill: float = 0.0,
               unroll: int = 1) -> jax.Array:
    """One stage of a radix-``fan_in`` prefix circuit: fold the fan_in - 1
    shifted neighbours at multiples of ``stride`` into every element."""
    tile_n = x.shape[-1]
    shifted = [shift_lanes(x, k * stride, fill) for k in range(1, fan_in)
               if k * stride < tile_n]
    if not shifted:
        return x
    if unroll > 1:
        return x + _tree_fold(shifted)
    acc = x
    for sh in shifted:
        acc = acc + sh
    return acc


def linrec_level(aa: jax.Array, bb: jax.Array, fan_in: int, stride: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """One stage for the linear-recurrence pair monoid.

    Composition (a, b)_new after (a, b)_old is (a_o * a_n, a_n * b_o + b_n);
    the fold order is fixed by the algebra, so there is no unroll knob —
    the search spaces prune it for linrec variants.
    """
    tile_n = aa.shape[-1]
    acc_a, acc_b = aa, bb
    for k in range(1, fan_in):
        off = k * stride
        if off >= tile_n:
            break
        sa = shift_lanes(aa, off, 1.0)    # identity transform: a = 1
        sb = shift_lanes(bb, off, 0.0)    # identity transform: b = 0
        acc_b = acc_a * sb + acc_b
        acc_a = acc_a * sa
    return acc_a, acc_b


# ---------------------------------------------------------------------------
# Radix-r DIF butterfly stage (complex fold on split re/im planes)
# ---------------------------------------------------------------------------

def cmul(ar, ai, br, bi):
    return ar * br - ai * bi, ar * bi + ai * br


def dif_coefficients(n: int, stages: Sequence[int], inverse: bool):
    """Coefficient rows of the in-place DIF stages over an n-point row.

    Stage t of fan-in r and span h (n = blocks * r * h) maps element
    (block, j, q) to sum_k x(block, k, q) w_r^(jk) w_(rh)^(jq): every
    output is a sum over the 2r - 1 lane offsets d*h (d = k - j) of a
    neighbour times a per-lane complex coefficient, zero where k falls
    outside the block.  Returns (re, im) as (rows, n) float32 arrays, one
    row per (stage, offset), and the offsets per stage.
    """
    sign = 1.0 if inverse else -1.0
    lane = np.arange(n)
    re, im, offsets = [], [], []
    span = n
    for r in stages:
        h = span // r
        q, j = lane % h, (lane // h) % r
        stage_offsets = []
        for d in range(1 - r, r):
            k = j + d
            ang = sign * 2.0 * np.pi * (((j * k) % r) / r + (j * q) / span)
            live = (k >= 0) & (k < r)
            re.append(np.where(live, np.cos(ang), 0.0))
            im.append(np.where(live, np.sin(ang), 0.0))
            stage_offsets.append(d * h)
        offsets.append(tuple(stage_offsets))
        span = h
    return (np.asarray(re, np.float32), np.asarray(im, np.float32),
            tuple(offsets))


def butterfly(re: jax.Array, im: jax.Array, coef_re, coef_im, row: int,
              offsets: Sequence[int]) -> Tuple[jax.Array, jax.Array]:
    """One radix-r DIF stage on (rows, n) split planes, in place.

    Each lane folds its 2r - 1 shifted neighbours (``shift_lanes`` at the
    stage's offsets) through its coefficient rows ``row .. row + 2r - 2``
    of ``coef_re``/``coef_im`` (refs of ``dif_coefficients``), so a stage
    is lane shifts and elementwise complex multiply-adds — no lane
    shuffle or reshape, which Mosaic cannot lower across the lane dim.
    The outputs land in digit-reversed order (``digit_reverse_rows``).
    """
    acc_re = jnp.zeros_like(re)
    acc_im = jnp.zeros_like(im)
    for i, off in enumerate(offsets):
        cr = coef_re[row + i:row + i + 1, :]
        ci = coef_im[row + i:row + i + 1, :]
        tr, ti = cmul(shift_lanes(re, -off, 0.0), shift_lanes(im, -off, 0.0),
                      cr, ci)
        acc_re = acc_re + tr
        acc_im = acc_im + ti
    return acc_re, acc_im


def digit_reverse(y: jax.Array, stages: Sequence[int]) -> jax.Array:
    """Reorder (batch, n) DIF outputs into natural order (an XLA
    reshape/transpose outside the kernel): position (j1, .., jk), j1 most
    significant, holds frequency j1 + r1 j2 + ...  The launches of an n
    that is not a power of two use it (``plan.fft_reorder_site``)."""
    batch, n = y.shape
    k = len(stages)
    y = y.reshape((batch,) + tuple(int(r) for r in stages))
    return jnp.transpose(y, (0,) + tuple(range(k, 0, -1))).reshape(batch, n)


def _log2(v: int) -> int:
    return int(v).bit_length() - 1


def _lo_stages(stages: Sequence[int], n: int) -> int:
    """How many leading stages the in-VMEM reversal gathers at once: the
    fewest whose fan-ins multiply to a whole sublane tile (8 rows), or to
    n when n is smaller."""
    lo, size = 0, 1
    while size < min(8, n):
        size *= stages[lo]
        lo += 1
    return lo


def _lo_natural(c, stages: Sequence[int], lo: int):
    """Natural index (r1 least significant) of the leading ``lo`` digits
    whose DIF position (r1 most significant) is ``c``: a bit-field
    permutation, so it adds over disjoint bits and takes traced ints."""
    span = 1
    for r in stages[:lo]:
        span *= r
    out, weight = 0, 1
    for r in stages[:lo]:
        span //= r
        out = out + (((c >> _log2(span)) & (r - 1)) << _log2(weight))
        weight *= r
    return out


def _hi_position(f, stages: Sequence[int], lo: int, n: int):
    """DIF row offset of the trailing digits (after the first ``lo``)
    whose natural index (r_lo+1 least significant) is ``f``; additive over
    disjoint bits like ``_lo_natural``."""
    out, shift, span = 0, 0, n
    for t, r in enumerate(stages):
        span //= r
        if t >= lo:
            out = out + (((f >> shift) & (r - 1)) << _log2(span))
            shift += _log2(r)
    return out


# lanes of one vector register: the reversal transposes (128, 128) tiles
LANE_TILE = 128


def digit_reverse_rows(ref, tmp_ref, stages: Sequence[int]) -> None:
    """Rewrite the (rows, n) DIF outputs held in ``ref`` in natural order,
    inside the kernel; n a power of two (``plan.fft_reorder_site``).

    Mosaic cannot reshape a row into its digit axes, so the reversal goes
    through ``tmp_ref``, an (n, min(rows, 128)) VMEM scratch in which the
    transform index runs down sublanes and the problems across lanes.
    Split n = R * M, R the fan-ins of the first stages (``_lo_stages``):
    row c * M + h (c their DIF digits) holds frequency lo(c) + R * hi(h).

      * each chunk of 128 lanes is transposed into ``tmp_ref``, its M-row
        blocks stored at lo(c) * M: a static permutation of whole blocks;
      * each chunk of 128 output frequencies gathers 128 / R strided loads
        of R rows at stride M (frequencies lo = 0 .. R-1 of one hi),
        transposed back into ``ref``.

    Both passes loop over the n / 128 chunks (``fori_loop``), so the
    unrolled code is one chunk's whatever n is.
    """
    if len(stages) < 2:                   # one digit: already in order
        return
    rows, n = ref.shape
    lo = _lo_stages(stages, n)
    size = 1
    for r in stages[:lo]:
        size *= r
    span = n // size
    chunk = min(n, LANE_TILE)
    chunks = n // chunk
    lanes = tmp_ref.shape[1]

    def lane_slice(q):
        if chunks == 1:
            return slice(None)
        return pl.ds(pl.multiple_of(q * chunk, chunk), chunk)

    def sublane_start(start, align):
        return start if chunks == 1 else pl.multiple_of(start, align)

    def loop(body):
        if chunks == 1:
            body(0, 0)
        else:
            jax.lax.fori_loop(0, chunks, body, 0)

    for g in range(0, rows, lanes):
        def scatter(q, carry):
            t = ref[pl.ds(g, lanes), lane_slice(q)].T       # (chunk, lanes)
            first = (q * chunk) >> _log2(span)
            if span >= chunk:
                dest = (_lo_natural(first, stages, lo) * span
                        + (q * chunk) % span)
                tmp_ref[pl.ds(sublane_start(dest, chunk), chunk), :] = t
            else:
                base = _lo_natural(first, stages, lo) * span
                for b in range(chunk // span):
                    dest = base + _lo_natural(b, stages, lo) * span
                    tmp_ref[pl.ds(sublane_start(dest, span), span), :] = \
                        t[b * span:(b + 1) * span]
            return carry

        def gather(q, carry):
            per = chunk // size
            base = _hi_position(q * per, stages, lo, n)
            parts = [tmp_ref[pl.ds(base + _hi_position(i, stages, lo, n),
                                   size, stride=span), :]
                     for i in range(per)]
            t = jnp.concatenate(parts, axis=0) if per > 1 else parts[0]
            ref[pl.ds(g, lanes), lane_slice(q)] = t.T
            return carry

        loop(scatter)
        loop(gather)


# ---------------------------------------------------------------------------
# PCR reduction step (the tridiagonal fold)
# ---------------------------------------------------------------------------

def pcr_step(a, b, c, d, stride: int):
    """One full-width cyclic-reduction level at ``stride``: every equation
    eliminates its +-stride neighbours (identity fill keeps pivots finite)."""
    bm = shift_lanes(b, stride, 1.0)
    bp = shift_lanes(b, -stride, 1.0)
    am, ap = shift_lanes(a, stride, 0.0), shift_lanes(a, -stride, 0.0)
    cm, cp = shift_lanes(c, stride, 0.0), shift_lanes(c, -stride, 0.0)
    dm, dp = shift_lanes(d, stride, 0.0), shift_lanes(d, -stride, 0.0)
    alpha = -a / bm
    gamma = -c / bp
    return (alpha * am,
            b + alpha * cm + gamma * ap,
            gamma * cp,
            d + alpha * dm + gamma * dp)


# ---------------------------------------------------------------------------
# Cross-tile carry chain
# ---------------------------------------------------------------------------

def carry_init(carry_ref, axis: int = 1) -> None:
    """Zero the VMEM carry on the first sequential tile of ``axis``."""
    @pl.when(pl.program_id(axis) == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)


def carry_fold_add(x: jax.Array, carry_ref) -> jax.Array:
    """Fold the running prefix into this tile; store the new carry."""
    x = x + carry_ref[...]
    carry_ref[...] = x[:, -1:]
    return x


def carry_fold_linrec(aa: jax.Array, bb: jax.Array, carry_ref) -> jax.Array:
    """h = b + a * carry for the tile; store the tile's exit state."""
    h = bb + aa * carry_ref[...]
    carry_ref[...] = h[:, -1:]
    return h


# ---------------------------------------------------------------------------
# Elementwise ops folded into a stage loop's prologue
# ---------------------------------------------------------------------------

def rglru_gate(aa: jax.Array, uu: jax.Array) -> jax.Array:
    """RG-LRU input gate b = sqrt(max(1 - a^2, 0)) * u, in-tile.

    The rglru op runs this as the scan kernel's first stage
    (``gate=True``) instead of a separate XLA pass over the rows.
    """
    return jnp.sqrt(jnp.maximum(1.0 - aa * aa, 0.0)) * uu


# ---------------------------------------------------------------------------
# Stage-sequence helpers shared by the kernel wrappers
# ---------------------------------------------------------------------------

def as_stages(stages: Sequence[int]) -> Tuple[int, ...]:
    """Normalize a plan's stage sequence into a hashable static argument."""
    return tuple(int(r) for r in stages)
