"""StagePlan — the staged-execution planner shared by kernels, the
analytical model, and ML featurization.

BPLG's central idea is that FFT, scan and tridiagonal solvers are all
compositions of the *same* tuned CTA-level building blocks (radix-r
staging, layout shuffles, carry chaining).  The repo analogue: given
``(Workload, Config)`` this module produces the exact staged execution —
the per-stage radix sequence (with the mixed-radix ragged final stage),
the launch grid / block shapes / scratch, each launch's VMEM bytes, and
the HBM pass count (== number of kernel launches the driver performs).

A launch's VMEM is what Mosaic allocates for it: the double-buffered
pipeline blocks, the scratch, and the temporaries of the in-kernel fold,
every buffer padded to the (sublane, lane) tile.  The temporaries are
bounds fitted to compiles for a described TPU v5e (the largest live set
of each fold, in blocks); ``HardwareProfile.vmem_budget`` is the limit
the kernels are compiled under, so a plan within it is a kernel the
compiler accepts.

It is the single source of truth: the kernel drivers execute
``plan.launches`` verbatim, ``core.analytical.resources`` reads its
fields instead of re-deriving pass counts from knobs, and
``tuning.ml.features`` featurizes the same fields — so model and kernel
cannot silently disagree (tests/test_blocks_plan.py pins the agreement).

Composite ops run one chain each: rglru's gate is the scan kernel's
first stage, and the SSD is an intra-chunk launch followed, when there is
more than one chunk, by one sequential launch for the inter-chunk
recurrence and its apply.  Their ``launches`` and ``passes`` are the
whole contract: a ``capture_launches`` trace equals ``plan.launches``.

Deliberately pure Python (no jax import): the analytical tuner and the
numpy-only ML stack consume plans without pulling in the kernel runtime.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import Dict, List, Mapping, Optional, Tuple

from repro.core.space import Workload, fit_block
from repro.hw.profiles import (HardwareProfile, active_profile, dtype_bytes,
                               effective_element_bytes, lane_utilization,
                               sublane_utilization)

# Column tiles a fused carry chain tolerates before the multi-pass driver
# (three launches, parallel across chunks) wins over serializing the grid's
# sequential dimension — the paper's §IV-C small/large-N boundary.
DEFAULT_SEQ_LIMIT = 64

# Variants whose in-kernel state is an (a, b) pair: three resident planes
# (two inputs + output) instead of two.
_LINREC_VARIANTS = ("linrec",)

# f32 (rows, tile) temporaries alive at the peak of one fold stage of
# fan-in r, beyond the pipeline buffers: the r - 1 shifted neighbours plus
# the accumulator (prefix sum), or shifted (a, b) pairs plus both
# accumulators (linear recurrence).  Fitted with margin to the compiler's
# scoped allocations on v5e (r = 2, 4, 8; tiles of 128 to 4096 lanes).
_ADD_FOLD_TEMPS = 1          # + r
_LINREC_FOLD_TEMPS = 4       # + 2 r
# a radix-r DIF stage keeps its 2r - 1 shifted (re, im) neighbours and
# their sublane-broadcast coefficient rows live
_FFT_FOLD_TEMPS = 10         # + 4 r
# PCR keeps the four coefficient planes, their eight shifted neighbours
# and the two elimination factors live in one step
_PCR_TEMPS = 18
# the SSD chunk kernels hold a few (Q, Q) f32 score/decay tiles
_SSD_QQ_TEMPS = 4
# bf16 matrix-unit passes of one f32 contraction at Precision.HIGHEST
_SSD_MXU_PASSES = 6
# flash attention holds the (block_q, block_k) scores and probabilities
_FLASH_QK_TEMPS = 2
# head / state widths a Workload does not carry: the planner assumes one
# lane tile (the registered archs' head_dim, ssm head_dim and state <= 128)
_MODEL_MINOR = 128


# ---------------------------------------------------------------------------
# Mixed-radix stage decomposition
# ---------------------------------------------------------------------------

def _smallest_prime_factor(n: int) -> int:
    if n % 2 == 0:
        return 2
    f = 3
    while f * f <= n:
        if n % f == 0:
            return f
        f += 2
    return n


def stage_radices(n: int, radix: int) -> Tuple[int, ...]:
    """Per-stage fan-in sequence for an n-point staged circuit.

    Generalizes the FFT kernel's ``rr = min(radix, n_cur)`` and the scan
    kernel's ``_ks_levels``: each stage takes the preferred fan-in when it
    divides what is left, else the largest divisor <= radix (the ragged
    mixed-radix final stage), else the smallest prime factor.  Invariant
    (pinned by tests): ``prod(stage_radices(n, r)) == n`` for every n >= 1,
    so a stage loop driven by this sequence can never mis-reshape — unlike
    the historical per-kernel loops, which crashed whenever an intermediate
    ``n_cur`` stopped dividing by the radix (e.g. radix 8 at n = 96).
    """
    n = int(n)
    radix = max(int(radix), 2)
    out = []
    n_cur = n
    while n_cur > 1:
        rr = min(radix, n_cur)
        if n_cur % rr:
            divisors = [d for d in range(rr, 1, -1) if n_cur % d == 0]
            rr = divisors[0] if divisors else _smallest_prime_factor(n_cur)
        out.append(rr)
        n_cur //= rr
    return tuple(out)


def stage_strides(stages: Tuple[int, ...]) -> Tuple[int, ...]:
    """Input stride of each stage: cumulative product of earlier fan-ins."""
    strides = []
    s = 1
    for r in stages:
        strides.append(s)
        s *= r
    return tuple(strides)


def shift_fold_counts(stages: Tuple[int, ...],
                      lane_count: int) -> Tuple[int, int]:
    """Lane-shifted folds per element of a Kogge-Stone stage loop.

    Stage ``i`` of fan-in ``r`` and stride ``s`` folds the neighbours at
    offsets ``k * s`` for ``k`` in 1 .. r - 1 (``shift_fold`` /
    ``linrec_level``).  Returns ``(in_vreg, cross_vreg)``: folds whose
    offset is below ``lane_count`` rotate and select lanes inside a vector
    register; the others only re-index whole registers.
    """
    in_vreg = cross_vreg = 0
    for r, stride in zip(stages, stage_strides(stages)):
        for k in range(1, r):
            if k * stride < lane_count:
                in_vreg += 1
            else:
                cross_vreg += 1
    return in_vreg, cross_vreg


def butterfly_offsets(n: int, stages: Tuple[int, ...]
                      ) -> Tuple[Tuple[int, ...], ...]:
    """Lane offsets each in-place DIF stage of an n-point row folds.

    Stage t of fan-in r and span h (the row is blocks of r * h lanes)
    reads the 2r - 1 neighbours at ``d * h`` for d in 1 - r .. r - 1, one
    complex term each; ``primitives.dif_coefficients`` builds one
    coefficient row per offset from this list.
    """
    out = []
    span = n
    for r in stages:
        h = span // r
        out.append(tuple(d * h for d in range(1 - r, r)))
        span = h
    return tuple(out)


def butterfly_counts(n: int, stages: Tuple[int, ...],
                     lane_count: int) -> Tuple[int, int, int]:
    """Work per element of the DIF stage loop (``butterfly_offsets``).

    Returns ``(terms, in_vreg, cross_vreg)``: complex multiply-adds, then
    the shifted neighbours among them whose offset is not a multiple of
    ``lane_count`` (rotated and selected inside a vector register) and
    those whose offset is (whole registers re-indexed).  Offset 0 is a
    term and no shift.
    """
    terms = in_vreg = cross_vreg = 0
    for offsets in butterfly_offsets(n, stages):
        terms += len(offsets)
        for off in offsets:
            if off % lane_count:
                in_vreg += 1
            elif off:
                cross_vreg += 1
    return terms, in_vreg, cross_vreg


def is_ragged(stages: Tuple[int, ...], nominal: int, span: int) -> bool:
    """Mixed-radix tail check shared by every plan builder.

    ``stage_radices`` only ever reduces the fan-in toward the tail, so a
    sequence is ragged exactly when its last stage falls short of the
    nominal fan-in (clamped by the circuit span for tiny tiles).  The
    analytical radix_rank and the ML ``ragged_tail`` feature both train
    on this flag — keep the definition in one place.
    """
    return bool(stages) and stages[-1] != min(nominal, span)


def fft_reorder_site(n: int) -> str:
    """Where an n-point FFT launch's digit reversal can run: ``"kernel"``
    (in VMEM, so the launch writes natural order) when n is a power of
    two, so every stage is too and the reversal is a permutation of bit
    fields; ``"xla"`` (a reshape/transpose after the launch) otherwise.
    The plan also sends it to XLA where its scratch would not fit."""
    return "kernel" if n & (n - 1) == 0 else "xla"


def _round_up(v: int, m: int) -> int:
    return -(-max(int(v), 1) // m) * m


def vmem_tile_bytes(rows: int, cols: int, itemsize: int,
                    spec: HardwareProfile) -> int:
    """Bytes one (rows, cols) VMEM buffer occupies: both minor dims pad to
    the profile's (sublane, lane) tile, so a (rows, 1) carry column costs
    a full lane tile per row group."""
    return (_round_up(rows, spec.sublane_count)
            * _round_up(cols, spec.lane_count) * itemsize)


def fold_vmem(rows: int, tile: int, stages: Tuple[int, ...], *, linrec: bool,
              io_planes: int, io_bytes: int, spec: HardwareProfile) -> int:
    """VMEM of one staged-fold launch over (rows, tile) blocks.

    Double-buffered pipeline blocks for every operand, the f32 carry
    column, and the fold's widest stage of f32 temporaries.
    """
    fan = max(stages, default=1)
    temps = (2 * fan + _LINREC_FOLD_TEMPS) if linrec \
        else (fan + _ADD_FOLD_TEMPS)
    return (2 * io_planes * vmem_tile_bytes(rows, tile, io_bytes, spec)
            + vmem_tile_bytes(rows, 1, 4, spec)
            + temps * vmem_tile_bytes(rows, tile, 4, spec))


def resident_tile_cap(wl: Workload,
                      spec: Optional[HardwareProfile] = None) -> int:
    """Largest power-of-two FFT length (from 256, at most ``wl.n``) whose
    smallest resident launch — one problem row, radix 2 — fits the VMEM
    the kernels compile under: the paper's §IV-C boundary between the
    resident kernel and the four-step path."""
    spec = spec if spec is not None else active_profile()
    tile = 256
    while tile * 2 <= wl.n and _fft_fused_plan(
            Workload(op="fft", n=tile * 2, batch=1, dtype=wl.dtype),
            {"rows_per_program": 1, "radix": 2}, spec).vmem_bytes \
            <= spec.vmem_budget:
        tile *= 2
    return tile


def wm_chunk(radix: int, n: int) -> int:
    """The Wang&Mou chunk implied by the tuned radix (paper: the fan-in).

    Lives here — not at the dispatch site — so the tridiag normalizer can
    put the derived chunk INTO the resolved config: what the TuningDB
    records then uniquely determines the executed kernel.
    """
    return fit_block(min(max(radix * 16, 8), max(n // 2, 1)), n)


# ---------------------------------------------------------------------------
# Plan dataclasses
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Launch:
    """One kernel launch the driver will perform."""

    name: str                       # kernel family tag (display/debug)
    grid: Tuple[int, ...]           # pallas grid
    block_shape: Tuple[int, int]    # main operand block (rows, cols)
    stages: Tuple[int, ...]         # in-kernel stage radices
    vmem_bytes: int                 # pipeline buffers + scratch + fold
    #                                 temporaries, tile-padded
    reorder: Optional[str] = None   # FFT: where the digit reversal runs,
    #                                 "kernel" | "xla" (fft_reorder_site)

    @property
    def programs(self) -> int:
        out = 1
        for g in self.grid:
            out *= g
        return out


@dataclasses.dataclass(frozen=True)
class StagePlan:
    """The exact staged execution of one (workload, config) pair."""

    op: str
    variant: str
    n: int
    batch: int
    dtype: str
    kind: str                       # "fused" | "multipass" | "two-phase"
    #                                 (ssd over several chunks) | "xla";
    #                                 dispatchers branch on == "multipass"
    tile_n: int                     # elements resident per program
    rows: int                       # problem rows per program
    radix: int                      # nominal (tuned) fan-in
    stages: Tuple[int, ...]         # per-stage radices of the resident tile
    seq_tiles: int                  # sequential carry tiles per program
    grid: Tuple[int, ...]           # main-launch grid
    launches: Tuple[Launch, ...]    # every kernel launch, driver order
    passes: int                     # HBM roundtrips == len(launches)
    #                                 when pallas-backed; 1 for fused XLA
    #                                 variants
    vmem_bytes: int                 # peak launch VMEM (Launch.vmem_bytes)
    block_bytes: int                # DMA block (analytical rank input)
    element_bytes: int              # effective bytes per logical element
    trailing: int                   # trailing-dim extent a VPU issue sees
    lane_eff: float                 # trailing-lane efficiency
    sublane_eff: float
    occupancy: float
    ilp: float
    ragged: bool                    # mixed-radix tail (last stage < radix)
    steps_per_pass: float
    children: Tuple["StagePlan", ...] = ()
    # (in-vreg, cross-vreg) lane-shifted folds per element when the stage
    # loop is a shift_fold / linrec_level circuit (``shift_fold_counts``);
    # None for butterfly, PCR and stage-less plans
    shift_folds: Optional[Tuple[int, int]] = None
    # modelled seconds per element of an SSD plan (``_ssd_chunk_cost``):
    # (intra-chunk matrix-unit work, the chunk's own costs spread over its
    # elements); None for every other op
    chunk_cost: Optional[Tuple[float, float]] = None
    # (complex terms, in-vreg shifts, cross-vreg shifts) per element of an
    # FFT's butterfly stages (``butterfly_counts``); None for every other op
    butterfly: Optional[Tuple[int, int, int]] = None

    @property
    def stage_count(self) -> int:
        return len(self.stages)

    @property
    def grid_size(self) -> int:
        out = 1
        for g in self.grid:
            out *= g
        return out

    def check(self, spec: HardwareProfile) -> List[str]:
        """Structural invariant violations of this plan ([] when sound).

        The zero-execution contract ``repro.analysis`` verifies for every
        valid config of every op x profile: a violation here means the
        planner would hand the drivers an execution that cannot launch
        (non-positive grid/block), mis-reshapes (stage product != tile),
        disagrees with its own pass accounting, or plans more VMEM for a
        launch than the limit kernels compile under.  Checks live on the
        dataclass so plan builders and the analysis pass can never drift
        apart.
        """
        out: List[str] = []
        if self.tile_n < 1 or self.rows < 1:
            out.append(f"non-positive tile geometry: tile_n={self.tile_n} "
                       f"rows={self.rows}")
        if self.passes < 1:
            out.append(f"non-positive pass count: {self.passes}")
        if self.vmem_bytes <= 0 or self.steps_per_pass <= 0:
            out.append(f"non-positive accounting: vmem={self.vmem_bytes} "
                       f"steps_per_pass={self.steps_per_pass}")
        if self.stages:
            prod = 1
            for r in self.stages:
                prod *= r
            if prod != self.tile_n:
                out.append(f"stage radix product {prod} != tile_n "
                           f"{self.tile_n} (stages={self.stages})")
        if any(g < 1 for g in self.grid):
            out.append(f"non-positive grid dim: {self.grid}")
        if self.launches and self.passes != len(self.launches):
            out.append(f"passes={self.passes} disagrees with "
                       f"{len(self.launches)} launches")
        for launch in self.launches:
            if any(g < 1 for g in launch.grid) \
                    or any(b < 1 for b in launch.block_shape):
                out.append(f"launch {launch.name}: non-positive shape "
                           f"grid={launch.grid} block={launch.block_shape}")
            if launch.vmem_bytes > spec.vmem_budget:
                out.append(f"launch {launch.name}: vmem {launch.vmem_bytes} "
                           f"exceeds the compile limit {spec.vmem_budget}")
            block = launch.block_shape[0] * launch.block_shape[1] \
                * self.element_bytes
            if launch.vmem_bytes < block:
                out.append(f"launch {launch.name}: scratch {launch.vmem_bytes}"
                           f" cannot hold its own BlockSpec block {block} "
                           f"({launch.block_shape} x {self.element_bytes}B)")
        return out

    def resources(self) -> Dict[str, float]:
        """Architectural accounting in the shape ``core.analytical`` scores.

        Every quantity is read off the plan — there is no independent
        re-derivation left in the analytical model or the featurizer.
        """
        folds = self.shift_folds or (0, 0)
        intra_s, chunk_s = self.chunk_cost or (0.0, 0.0)
        terms, in_vreg, cross_vreg = self.butterfly or (0, 0, 0)
        return {
            "grid": float(self.grid_size),
            "vmem": float(self.vmem_bytes),
            "occupancy": min(self.occupancy, 1.0),
            "ilp": float(self.ilp),
            "radix": float(self.radix),
            "passes": float(self.passes),
            "block_bytes": float(self.block_bytes),
            "seq_tiles": float(self.seq_tiles),
            "stage_count": float(self.stage_count),
            "steps_per_pass": float(self.steps_per_pass),
            "ragged": 1.0 if self.ragged else 0.0,
            "lane_eff": float(self.lane_eff),
            "sublane_eff": float(self.sublane_eff),
            "shift_circuit": 0.0 if self.shift_folds is None else 1.0,
            "lane_folds": float(folds[0]),
            "vreg_folds": float(folds[1]),
            "intra_s": float(intra_s),
            "chunk_s": float(chunk_s),
            "butterfly_terms": float(terms),
            "butterfly_in_vreg": float(in_vreg),
            "butterfly_cross_vreg": float(cross_vreg),
        }


# ---------------------------------------------------------------------------
# Per-family builders
# ---------------------------------------------------------------------------

def _occ(tile_n: int, rows: int, spec: HardwareProfile) -> Tuple[int, float, float, float]:
    trailing = min(tile_n, spec.lane_count * spec.sublane_count)
    lane = lane_utilization(trailing, spec)
    sub = sublane_utilization(rows, spec)
    return trailing, lane, sub, lane * max(sub, 0.5)


def _is_linrec(wl: Workload) -> bool:
    return wl.op in ("rglru",) or wl.variant in _LINREC_VARIANTS


def _prefix_plan(wl: Workload, cfg: Mapping[str, int], spec: HardwareProfile,
                 seq_limit: int) -> StagePlan:
    eb = effective_element_bytes(wl.op, wl.dtype)
    ib = dtype_bytes(wl.dtype)
    batch = max(wl.batch, 1)
    tile_n = min(int(cfg.get("tile_n", wl.n)), wl.n)
    rows = int(cfg.get("rows_per_program", 1))
    radix = int(cfg.get("radix", 2))
    unroll = int(cfg.get("unroll", 1))
    stages = stage_radices(tile_n, radix)
    seq_tiles = max(wl.n // max(tile_n, 1), 1)
    linrec = _is_linrec(wl)
    planes = 3 if linrec else 2                  # (a, b) in + h out vs in + out
    trailing, lane, sub, occ = _occ(tile_n, rows, spec)
    ragged = is_ragged(stages, radix, tile_n)
    # the resident tile's stage loop; the multi-pass carry scan folds one
    # element per tile, so its circuit is left out of the per-element count
    folds = shift_fold_counts(stages, spec.lane_count)

    if seq_tiles > seq_limit and tile_n < wl.n:
        # §IV-C m-kernel path: per-chunk scan, chunk-carry scan, apply.
        p, length = seq_tiles, tile_n
        rows1 = fit_block(rows, batch * p)
        rows2 = fit_block(rows, batch)
        c_stages = stage_radices(p, radix)
        # the driver runs every launch in f32.  linrec's chunk kernel
        # (scan_linrec_prod_pallas) keeps a fourth plane resident: the
        # per-chunk prefix-products output the carry scan composes; the
        # apply reads the chunks plus one entry column
        l1 = Launch("chunk-scan", (batch * p // rows1, 1), (rows1, length),
                    stages, fold_vmem(rows1, length, stages, linrec=linrec,
                                      io_planes=planes + (1 if linrec else 0),
                                      io_bytes=4, spec=spec))
        l2 = Launch("carry-scan", (batch // rows2, 1), (rows2, p),
                    c_stages, fold_vmem(rows2, p, c_stages, linrec=linrec,
                                        io_planes=planes, io_bytes=4,
                                        spec=spec))
        l3 = Launch("apply-entry", (batch * p // rows1,), (rows1, length),
                    (), 2 * (planes * vmem_tile_bytes(rows1, length, 4, spec)
                             + vmem_tile_bytes(rows1, 1, 4, spec)))
        launches = (l1, l2, l3)
        return StagePlan(
            op=wl.op, variant=wl.variant, n=wl.n, batch=batch, dtype=wl.dtype,
            kind="multipass", tile_n=tile_n, rows=rows, radix=radix,
            stages=stages, seq_tiles=seq_tiles, grid=l1.grid,
            launches=launches, passes=len(launches),
            vmem_bytes=max(l.vmem_bytes for l in launches),
            block_bytes=rows * tile_n * eb, element_bytes=eb,
            trailing=trailing, lane_eff=lane, sublane_eff=sub, occupancy=occ,
            ilp=unroll * (2 if cfg.get("in_register") else 1), ragged=ragged,
            steps_per_pass=float(len(stages)), shift_folds=folds)

    grid = (batch // rows, seq_tiles)
    launch = Launch(wl.op, grid, (rows, tile_n), stages,
                    fold_vmem(rows, tile_n, stages, linrec=linrec,
                              io_planes=planes, io_bytes=ib, spec=spec))
    return StagePlan(
        op=wl.op, variant=wl.variant, n=wl.n, batch=batch, dtype=wl.dtype,
        kind="fused", tile_n=tile_n, rows=rows, radix=radix, stages=stages,
        seq_tiles=seq_tiles, grid=grid, launches=(launch,),
        passes=1, vmem_bytes=launch.vmem_bytes,
        block_bytes=rows * tile_n * eb, element_bytes=eb, trailing=trailing,
        lane_eff=lane, sublane_eff=sub, occupancy=occ,
        ilp=unroll * (2 if cfg.get("in_register") else 1), ragged=ragged,
        steps_per_pass=float(len(stages)), shift_folds=folds)


def _ssd_chunk_cost(chunk: int, chunk_launches: int, spec: HardwareProfile
                   ) -> Tuple[float, float]:
    """Modelled seconds per element of the SSD at chunk length Q:
    (intra-chunk matrix-unit work, the chunk's own costs ÷ Q).

    The intra launch's three f32 contractions a chunk, C·Bᵀ (Q×S×Q),
    scores·x (Q×Q×P) and the chunk state (S×Q×P), run as
    ``_SSD_MXU_PASSES`` bf16 passes, each width rounded up to the unit's
    edge: 2·(Q·S + Q·P + S·P) flops an element, the first two growing with
    Q.  Each chunk adds its (S, P) f32 state, written by the intra launch
    and read by the carry, and one grid step of each of the
    ``chunk_launches`` launches that walk the chunks.  S and P are the
    model width ``_MODEL_MINOR``.
    """
    q, w = _round_up(chunk, spec.mxu_dim), _round_up(_MODEL_MINOR,
                                                     spec.mxu_dim)
    intra = (_SSD_MXU_PASSES * 2 * (2 * q * w + w * w)
             / spec.peak_bf16_flops)
    state = 2 * _MODEL_MINOR * _MODEL_MINOR * 4 / spec.hbm_bandwidth
    return intra, (state + chunk_launches * spec.grid_step_s) / chunk


def _ssd_plan(wl: Workload, cfg: Mapping[str, int], spec: HardwareProfile,
              seq_limit: int) -> StagePlan:
    """SSD: the intra-chunk launch, then — over several chunks — one
    sequential-grid launch whose VMEM carry holds the running (S, P) entry
    state, so the chunk states feed the recurrence without leaving the
    core and the apply shares its launch (two-phase).  A single chunk
    needs the intra launch alone.  Neither runs a shift-fold circuit;
    both report ``_ssd_chunk_cost``."""
    base = _prefix_plan(wl, cfg, spec, seq_limit)
    chunk = base.tile_n
    nc = max(wl.n // max(chunk, 1), 1)
    # every chunk kernel holds (Q, Q) f32 tiles plus up to seven
    # double-buffered (Q, width) operand blocks (x, decay column and row,
    # B, C, y, state)
    chunk_vmem = (2 * 7 * vmem_tile_bytes(chunk, _MODEL_MINOR, 4, spec)
                  + _SSD_QQ_TEMPS * vmem_tile_bytes(chunk, chunk, 4, spec))
    intra = Launch("ssd-intra", (base.batch, nc), (1, chunk), (), chunk_vmem)
    if nc <= 1:
        return dataclasses.replace(base, kind="fused", seq_tiles=1,
                                   launches=(intra,), vmem_bytes=chunk_vmem,
                                   shift_folds=None,
                                   chunk_cost=_ssd_chunk_cost(chunk, 1, spec))
    state_apply = Launch("ssd-state-apply", (base.batch, nc), (1, chunk), (),
                         chunk_vmem)
    launches = (intra, state_apply)
    return dataclasses.replace(
        base, kind="two-phase", seq_tiles=nc, launches=launches,
        passes=len(launches), vmem_bytes=chunk_vmem, shift_folds=None,
        chunk_cost=_ssd_chunk_cost(chunk, 2, spec))


def _tridiag_plan(wl: Workload, cfg: Mapping[str, int], spec: HardwareProfile
                  ) -> StagePlan:
    eb = effective_element_bytes(wl.op, wl.dtype)        # 4 coefficients
    ib = dtype_bytes(wl.dtype)
    batch = max(wl.batch, 1)
    rows = int(cfg.get("rows_per_program", 1))
    radix = int(cfg.get("radix", 2))
    n = wl.n
    trailing, lane, sub, occ = _occ(n, rows, spec)
    ilp = int(cfg.get("unroll", 1)) * (2 if cfg.get("in_register") else 1)

    if wl.variant == "pcr":
        steps = max(1, math.ceil(math.log2(max(n, 2))))
        stages = (2,) * steps
        # a,b,c,d in + x out, double-buffered, plus one step's temporaries
        vmem = (2 * 5 * vmem_tile_bytes(rows, n, ib, spec)
                + _PCR_TEMPS * vmem_tile_bytes(rows, n, 4, spec))
        grid = (batch // rows,)
        launch = Launch("pcr", grid, (rows, n), stages, vmem)
        return StagePlan(
            op=wl.op, variant=wl.variant, n=n, batch=batch, dtype=wl.dtype,
            kind="fused", tile_n=n, rows=rows, radix=2, stages=stages,
            seq_tiles=1, grid=grid, launches=(launch,), passes=1,
            vmem_bytes=vmem, block_bytes=rows * n * eb, element_bytes=eb,
            trailing=trailing,
            lane_eff=lane, sublane_eff=sub, occupancy=occ, ilp=ilp,
            ragged=False, steps_per_pass=float(steps))

    # XLA-fused variants (cr / lf / wm / thomas): no pallas launches; the
    # logical circuit still has a stage structure the models consume
    # (for wm the nominal fan-in is the tuned radix; cr/lf/thomas halve).
    nominal = radix if wl.variant == "wm" else 2
    stages = stage_radices(n, nominal)
    vmem = rows * n * eb * 2                    # double-buffered row estimate
    ragged = is_ragged(stages, nominal, n)
    return StagePlan(
        op=wl.op, variant=wl.variant, n=n, batch=batch, dtype=wl.dtype,
        kind="xla", tile_n=n, rows=rows, radix=radix, stages=stages,
        seq_tiles=1, grid=(batch // max(rows, 1),), launches=(), passes=1,
        vmem_bytes=vmem, block_bytes=rows * n * eb, element_bytes=eb,
        trailing=trailing,
        lane_eff=lane, sublane_eff=sub, occupancy=occ, ilp=ilp,
        ragged=ragged, steps_per_pass=float(max(len(stages), 1)))


def _fft_fused_plan(wl: Workload, cfg: Mapping[str, int], spec: HardwareProfile
                    ) -> StagePlan:
    eb = effective_element_bytes("fft", wl.dtype)        # interleaved re/im
    batch = max(wl.batch, 1)
    rows = fit_block(int(cfg.get("rows_per_program", 4)), batch)
    radix = int(cfg.get("radix", 2))
    n = wl.n
    stages = stage_radices(n, radix)
    # re/im in + re/im out double-buffered, the (re, im) DIF coefficient
    # tables (one row per stage offset), and the widest stage's shifted
    # neighbours and accumulators
    coef_rows = sum(2 * r - 1 for r in stages)
    vmem = (2 * 4 * vmem_tile_bytes(rows, n, 4, spec)
            + 2 * 2 * vmem_tile_bytes(coef_rows, n, 4, spec)
            + (_FFT_FOLD_TEMPS + 4 * max(stages, default=1))
            * vmem_tile_bytes(rows, n, 4, spec))
    # the in-VMEM reversal (``primitives.digit_reverse_rows``): the
    # transposed (n, rows) scratch, its lanes padded to a whole tile at
    # small rows, and one chunk of lanes in flight both ways.  Where that
    # would not fit (large n), the launch keeps the XLA reorder, so no
    # config the space admitted without it drops out
    lanes = min(rows, spec.lane_count)
    chunk = min(n, spec.lane_count)
    reorder_vmem = (vmem_tile_bytes(n, lanes, 4, spec)
                    + 2 * vmem_tile_bytes(chunk, lanes, 4, spec)
                    + vmem_tile_bytes(lanes, chunk, 4, spec))
    reorder = fft_reorder_site(n)
    if reorder == "kernel" and vmem + reorder_vmem <= spec.vmem_budget:
        vmem += reorder_vmem
    else:
        reorder = "xla"
    trailing, lane, sub, occ = _occ(n, rows, spec)
    grid = (batch // rows,)
    launch = Launch("fft", grid, (rows, n), stages, vmem, reorder)
    return StagePlan(
        op="fft", variant=wl.variant, n=n, batch=batch, dtype=wl.dtype,
        kind="fused", tile_n=n, rows=rows, radix=radix, stages=stages,
        seq_tiles=1, grid=grid, launches=(launch,), passes=1, vmem_bytes=vmem,
        block_bytes=rows * n * eb, element_bytes=eb, trailing=trailing,
        lane_eff=lane, sublane_eff=sub, occupancy=occ,
        ilp=int(cfg.get("unroll", 1)), ragged=is_ragged(stages, radix, n),
        steps_per_pass=float(len(stages)),
        butterfly=butterfly_counts(n, stages, spec.lane_count))


def _large_fft_plan(wl: Workload, cfg: Mapping[str, int], spec: HardwareProfile,
                    seq_limit: int, max_tile: Optional[int]) -> StagePlan:
    """Four-step decomposition N = n1*n2 (paper §IV-C), recursive.

    Column FFTs (length n2) and row FFTs (length n1) are child plans; the
    launch list is their concatenation, so ``passes`` counts exactly the
    kernel launches the driver performs (m = 2, or 3 when the column side
    recurses — the paper's N >= 2^19 case on its 48KB-tile device).
    """
    cap = max_tile if max_tile is not None else resident_tile_cap(wl, spec)
    batch = max(wl.batch, 1)
    n = wl.n
    n1 = fit_block(min(int(cfg.get("tile_n", cap)), cap), n)
    n2 = max(n // n1, 1)
    sub_cfg = dict(cfg)
    sub_cfg["tile_n"] = n1
    col_wl = Workload(op="fft" if n2 <= cap else "large_fft", n=n2,
                      batch=batch * n1, dtype=wl.dtype, variant=wl.variant)
    col = build_plan(col_wl, sub_cfg, profile=spec, seq_limit=seq_limit,
                     max_tile=cap)
    row = _fft_fused_plan(
        Workload(op="fft", n=n1, batch=batch * n2, dtype=wl.dtype,
                 variant=wl.variant), sub_cfg, spec)
    launches = col.launches + row.launches
    return StagePlan(
        op=wl.op, variant=wl.variant, n=n, batch=batch, dtype=wl.dtype,
        kind="multipass", tile_n=n1, rows=row.rows, radix=row.radix,
        stages=row.stages, seq_tiles=1, grid=row.grid, launches=launches,
        passes=len(launches), vmem_bytes=max(p.vmem_bytes for p in (col, row)),
        block_bytes=row.block_bytes,
        element_bytes=row.element_bytes, trailing=row.trailing,
        lane_eff=row.lane_eff, sublane_eff=row.sublane_eff,
        occupancy=row.occupancy, ilp=row.ilp, ragged=row.ragged,
        steps_per_pass=row.steps_per_pass, children=(col, row),
        # every element passes through one column and one row transform
        butterfly=tuple(c + r for c, r in zip(col.butterfly, row.butterfly)))


def _attention_plan(wl: Workload, cfg: Mapping[str, int], spec: HardwareProfile
                    ) -> StagePlan:
    batch = max(wl.batch, 1)
    eb = effective_element_bytes(wl.op, wl.dtype)
    bq = int(cfg.get("block_q", 128))
    bk = int(cfg.get("block_k", 128))
    grid = (batch * max(wl.n // bq, 1),)
    d = _MODEL_MINOR
    blocks = (bq + 2 * bk) * d * eb            # q, k, v blocks (the DMA set)
    # double-buffered q/k/v/o blocks, the f32 running max / denominator /
    # accumulator scratch, and the (bq, bk) f32 scores + probabilities
    vmem = (2 * (2 * vmem_tile_bytes(bq, d, eb, spec)
                 + 2 * vmem_tile_bytes(bk, d, eb, spec))
            + 2 * vmem_tile_bytes(bq, 1, 4, spec)
            + vmem_tile_bytes(bq, d, 4, spec)
            + _FLASH_QK_TEMPS * vmem_tile_bytes(bq, bk, 4, spec))
    steps = max(wl.n // bk, 1)
    return StagePlan(
        op=wl.op, variant=wl.variant, n=wl.n, batch=batch, dtype=wl.dtype,
        kind="fused", tile_n=bk, rows=bq, radix=2, stages=(),
        seq_tiles=steps, grid=grid, launches=(), passes=1, vmem_bytes=vmem,
        block_bytes=blocks, element_bytes=eb,
        trailing=bk, lane_eff=lane_utilization(bk, spec),
        sublane_eff=sublane_utilization(bq, spec),
        occupancy=lane_utilization(bk, spec),
        # the flash kernel has no unroll knob (its inner loop IS the
        # block_k walk), so the plan must not report phantom ILP from one
        ilp=1, ragged=False,
        steps_per_pass=float(steps))


def _matmul_plan(wl: Workload, cfg: Mapping[str, int], spec: HardwareProfile
                 ) -> StagePlan:
    batch = max(wl.batch, 1)
    eb = effective_element_bytes(wl.op, wl.dtype)
    bm = int(cfg.get("block_m", 128))
    bn = int(cfg.get("block_n", 128))
    bk = int(cfg.get("block_k", 128))
    grid = (max(batch // bm, 1), max(wl.n // bn, 1))
    vmem = (bm * bk + bk * bn) * eb * 2
    occ = min(bn / spec.mxu_dim, 1.0) * min(bm / spec.mxu_dim, 1.0)
    steps = max(wl.n // bk, 1)
    return StagePlan(
        op=wl.op, variant=wl.variant, n=wl.n, batch=batch, dtype=wl.dtype,
        kind="fused", tile_n=bn, rows=bm, radix=2, stages=(),
        seq_tiles=steps, grid=grid, launches=(), passes=1, vmem_bytes=vmem,
        block_bytes=vmem // 2, element_bytes=eb,
        trailing=bn, lane_eff=lane_utilization(bn, spec),
        sublane_eff=sublane_utilization(bm, spec), occupancy=occ,
        ilp=bk // 128 or 1, ragged=False, steps_per_pass=float(steps))


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def _resolve_profile(profile: Optional[HardwareProfile],
                     spec: Optional[HardwareProfile]) -> HardwareProfile:
    """Canonical ``profile=`` with the deprecated ``spec=`` alias."""
    if spec is not None:
        warnings.warn("spec=... is deprecated; pass profile=...",
                      DeprecationWarning, stacklevel=3)
        if profile is None:
            profile = spec
    return profile if profile is not None else active_profile()


def build_plan(wl: Workload, cfg: Mapping[str, int], *,
               profile: Optional[HardwareProfile] = None,
               spec: Optional[HardwareProfile] = None,
               seq_limit: int = DEFAULT_SEQ_LIMIT,
               max_tile: Optional[int] = None) -> StagePlan:
    """The staged execution of ``cfg`` on ``wl`` (uncached; see plan_for).

    ``profile`` is the canonical device argument; ``spec=`` is a
    deprecated alias from the pre-policy API.
    """
    wl = wl.canonical()
    spec = _resolve_profile(profile, spec)
    if wl.op in ("scan", "ssd", "rglru"):
        if wl.op == "ssd":
            return _ssd_plan(wl, cfg, spec, seq_limit)
        return _prefix_plan(wl, cfg, spec, seq_limit)
    if wl.op == "tridiag":
        return _tridiag_plan(wl, cfg, spec)
    if wl.op == "fft":
        return _fft_fused_plan(wl, cfg, spec)
    if wl.op == "large_fft":
        return _large_fft_plan(wl, cfg, spec, seq_limit, max_tile)
    if wl.op == "attention":
        return _attention_plan(wl, cfg, spec)
    if wl.op == "matmul":
        return _matmul_plan(wl, cfg, spec)
    # unknown op: a degenerate single-launch plan keeps generic consumers
    # (featurizer, analytical tiering) total rather than raising
    return _prefix_plan(wl, cfg, spec, seq_limit)


@functools.lru_cache(maxsize=65536)
def _plan_cached(op: str, variant: str, n: int, batch: int, dtype: str,
                 cfg_items: Tuple[Tuple[str, int], ...], spec: HardwareProfile,
                 seq_limit: int, max_tile: Optional[int]) -> StagePlan:
    wl = Workload(op=op, n=n, batch=batch, dtype=dtype, variant=variant)
    return build_plan(wl, dict(cfg_items), profile=spec, seq_limit=seq_limit,
                      max_tile=max_tile)


def plan_for(wl: Workload, cfg: Mapping[str, int], *,
             profile: Optional[HardwareProfile] = None,
             spec: Optional[HardwareProfile] = None,
             seq_limit: int = DEFAULT_SEQ_LIMIT,
             max_tile: Optional[int] = None) -> StagePlan:
    """Memoized ``build_plan`` — the resolve/dispatch hot path and the
    featurizer hit the same plan thousands of times per space.

    ``profile`` is the canonical device argument; ``spec=`` is a
    deprecated alias from the pre-policy API.
    """
    wl = wl.canonical()
    spec = _resolve_profile(profile, spec)
    return _plan_cached(wl.op, wl.variant, wl.n, wl.batch, wl.dtype,
                        tuple(sorted(cfg.items())), spec, seq_limit, max_tile)
