"""Analytical model-driven tuning (paper §IV-A, adapted to TPU per DESIGN.md §2).

Zero-evaluation tuner: scores every valid configuration with an ordinal
occupancy model and returns the argmax. This is the *online* methodology —
it answers immediately from architectural reasoning, exactly like the paper's
guideline answers from the GM20B occupancy table (Fig 3a).

TPU guideline (re-derivation of the paper's four rules):
  1. Prefer configs achieving BOTH full pipeline overlap (>= OVERLAP_GRID
     grid programs, double-buffered VMEM fit) AND full lane utilization.
  2. Else maximize grid parallelism while lane utilization stays in
     [0.60, 1.00] (the paper's warp-occupancy band).
  3. Else maximize lane utilization; among ties prefer larger unroll (ILP).
  4. Rank the stage circuit, exact (every stage at the nominal fan-in)
     before ragged, even at reduced grid parallelism.  Where stages pay a
     barrier (``HardwareProfile.stage_sync_s`` > 0, the paper's GPU case)
     prefer the larger radix: fewer stages, fewer sync points.  Where they
     do not (a TPU core runs a Pallas body's stages as straight-line
     vector code), a stage costs the work in it: a radix-r Kogge-Stone
     stage folds r - 1 lane-shifted neighbours, and a fold whose offset is
     below the lane count rotates and selects inside a vector register
     while a farther one only re-indexes whole registers.  So circuits of
     ``shift_fold`` / ``linrec_level`` stages rank by fewer in-vreg folds,
     then fewer cross-vreg folds (which picks radix 2 for power-of-two
     tiles); FFT butterflies and tridiagonal stages keep the larger radix.

Ahead of rule 4, after the tier and the pass count, a carry chain ranks
by fewer sequential tiles.  An SSD chain is the exception: its phase A
runs quadratic contractions over each chunk, so its work per element
grows with the chunk length Q, while a chunk's own cost (its state's HBM
roundtrip, a grid step per launch) shrinks per element as Q grows.  The
plan models both (``intra_s`` and ``chunk_s``, seconds per element) and
the chain ranks by their sum, which picks the chunk that balances them.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

from repro.core.space import Config, SearchSpace
from repro.hw.profiles import HardwareProfile

OVERLAP_GRID = 4          # grid programs needed for full DMA/compute overlap
OCCUPANCY_BAND = (0.60, 1.00)

# keys every resources() dict carries (the plan <-> model contract);
# repro.analysis verifies presence and finiteness for every valid config
# of every op x profile, so the expert model can never silently read a
# missing quantity as 0
RESOURCE_KEYS = ("grid", "vmem", "occupancy", "ilp", "radix", "passes",
                 "block_bytes", "seq_tiles", "stage_count", "steps_per_pass",
                 "ragged", "lane_eff", "sublane_eff", "shift_circuit",
                 "lane_folds", "vreg_folds", "intra_s", "chunk_s")


@dataclasses.dataclass
class AnalyticalScore:
    tier: int              # 3 = rule-1 configs, 2 = rule-2, 1 = rule-3 (higher better)
    pass_rank: float       # paper §IV-C premise: minimize the number of
    #                        passes/kernels FIRST (each extra pass is a full
    #                        HBM roundtrip) — ranks above the radix choice
    seq_rank: float        # TPU twist on the same premise: a fused carry
    #                        chain serializes its column tiles, so fewer
    #                        sequential tiles rank next; an SSD chain,
    #                        whose intra-chunk work grows with the chunk,
    #                        ranks by its modelled time (``_seq_rank``)
    circuit_rank: Tuple[float, float, float]   # rule 4: (exact, then the
    #                        circuit's cost; see ``_circuit_rank``)
    radix_rank: float      # rule 4 as the GPU guideline states it
    #                        (exact, then larger radix); kept as an ML
    #                        feature, the key reads ``circuit_rank``
    block_rank: float      # TPU adaptation of the paper's Ba maximization:
    #                        once >= OVERLAP_GRID programs keep the pipeline
    #                        full, BIGGER DMA blocks win (grid programs are
    #                        sequential per core, unlike CUDA blocks/SM)
    occupancy: float
    ilp_rank: float

    def key(self) -> Tuple:
        # Lexicographic: tier, then pass count (§IV-C), then carry-chain
        # depth, then the stage circuit (rule 4 overrides block choice),
        # then the tier-specific objective, then ILP tie-break.
        return (self.tier, self.pass_rank, self.seq_rank,
                *self.circuit_rank, self.block_rank, self.occupancy,
                self.ilp_rank)


def resources(space: SearchSpace, cfg: Config) -> Dict[str, float]:
    """Architectural resource accounting for one candidate config.

    Everything is read off the :class:`~repro.kernels.blocks.plan.StagePlan`
    — the exact staged execution the kernel drivers will launch — so the
    expert model and the kernels cannot disagree about pass counts, VMEM
    footprints or stage structure.  Public entry point for consumers that
    stack on the analytical model, notably ``repro.tuning.ml.features``.
    """
    # late import: repro.core.__init__ -> analytical must not re-enter
    # blocks.plan while the package is still initializing
    from repro.kernels.blocks.plan import plan_for

    return plan_for(space.workload, cfg, profile=space.spec).resources()


def score(space: SearchSpace, cfg: Config,
          res: Optional[Dict[str, float]] = None) -> AnalyticalScore:
    """Guideline score; pass ``res`` from :func:`resources` to avoid
    recomputing the accounting when the caller already has it."""
    if res is None:
        res = resources(space, cfg)
    spec = space.spec
    fits = res["vmem"] <= spec.vmem_budget
    full_overlap = res["grid"] >= OVERLAP_GRID and fits
    occ = res["occupancy"]
    lo, hi = OCCUPANCY_BAND

    if full_overlap and occ >= 0.999:
        tier = 3
    elif fits and lo <= occ <= hi:
        tier = 2
    elif fits:
        tier = 1
    else:
        tier = 0

    # rule 4: only stage sequences that stay at the nominal fan-in
    # throughout rank first; a ragged mixed-radix tail needs an extra odd
    # step and more synchronizations (the paper's own observation on WM's
    # jagged performance).  The raggedness comes from the plan's actual
    # stage sequence, not a re-derivation.
    exact = 0 if res.get("ragged") else 1
    radix_rank = exact * 16.0 + math.log2(max(res["radix"], 2))
    # TPU rule 1/2 objective: biggest DMA block that still leaves the
    # pipeline >= OVERLAP_GRID programs deep (saturating at 4 MiB, past
    # which the DMA ramp is flat).
    if res["grid"] >= OVERLAP_GRID:
        block_rank = math.log2(min(max(res["block_bytes"], 1), 4 * 2**20))
    else:
        block_rank = -1.0   # starves the pipeline: strictly worse
    return AnalyticalScore(tier, -res["passes"], _seq_rank(res),
                           _circuit_rank(res, spec, exact), radix_rank,
                           block_rank, occ, math.log2(max(res["ilp"], 1)))


def _seq_rank(res: Dict[str, float]) -> float:
    """The carry-chain term: fewer sequential tiles, or, where the plan
    models an SSD chain's time (module docstring), less of it."""
    chain_s = res["intra_s"] + res["chunk_s"]
    if chain_s > 0:
        return -chain_s
    return -math.log2(max(res.get("seq_tiles", 1), 1))


def _circuit_rank(res: Dict[str, float], spec: HardwareProfile,
                  exact: int) -> Tuple[float, float, float]:
    """Rule 4's rank of the stage circuit (module docstring): fewer
    lane-shifted folds for a shift-fold circuit on a barrier-free profile,
    else the larger radix."""
    if res["shift_circuit"] and spec.stage_sync_s == 0:
        return (float(exact), -res["lane_folds"], -res["vreg_folds"])
    return (float(exact), math.log2(max(res["radix"], 2)), 0.0)


class AnalyticalTuner:
    """Ranks the valid space with the guideline; no objective evaluations."""

    name = "analytical"

    def suggest(self, space: SearchSpace) -> Config:
        best: Optional[Config] = None
        best_key: Optional[Tuple] = None
        for cfg in space.enumerate_valid():
            k = score(space, cfg).key()
            if best_key is None or k > best_key:
                best, best_key = cfg, k
        if best is None:
            raise ValueError(f"search space for {space.workload.key} has no valid config")
        return best

    def rank(self, space: SearchSpace, top: int = 5) -> List[Config]:
        cfgs = space.enumerate_valid()
        cfgs.sort(key=lambda c: score(space, c).key(), reverse=True)
        return cfgs[:top]
