"""Search spaces for kernel performance parameters (paper Table I, TPU-native).

A space is declared per (operation, input-parameters) pair:
  - Input Parameters (paper: `A`): problem size N, batch G, dtype — they
    characterize the workload and are NOT searched.
  - Performance Parameters (paper: `B`): the tunable knobs with power-of-two
    domains and validity constraints.

`Config` is an immutable mapping knob-name -> value. Spaces are small and
enumerable (as in the paper), so `enumerate_valid()` is exact and the
exhaustive search is feasible — that property is what makes the Phi metric
computable.
"""
from __future__ import annotations

import dataclasses
import inspect
import itertools
import math
import warnings
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.hw.profiles import HardwareProfile, active_profile

Config = Dict[str, int]


def pow2_range(lo: int, hi: int) -> Tuple[int, ...]:
    """Inclusive powers of two from lo to hi."""
    assert lo > 0 and hi >= lo and lo & (lo - 1) == 0 and hi & (hi - 1) == 0
    out = []
    v = lo
    while v <= hi:
        out.append(v)
        v *= 2
    return tuple(out)


def floor_pow2(v: int) -> int:
    """Largest power of two <= v (v >= 1).

    Space builders bound their rows/tile domains with this so odd batch
    sizes (3 active serving slots, a ragged last shard) build a valid
    space instead of tripping ``pow2_range``'s power-of-two precondition.
    """
    v = int(v)
    assert v >= 1, v
    return 1 << (v.bit_length() - 1)


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """One Performance Parameter: a named discrete domain."""

    name: str
    domain: Tuple[int, ...]

    def index_of(self, value: int) -> int:
        return self.domain.index(value)


@dataclasses.dataclass(frozen=True)
class Workload:
    """Input Parameters `A`: what problem are we tuning for."""

    op: str                 # "scan" | "tridiag" | "fft" | "ssd" | "attention" | ...
    n: int                  # problem size (elements per problem / seq length)
    batch: int = 1          # simultaneous problems (paper: G batches)
    dtype: str = "float32"
    variant: str = ""       # e.g. "lf" | "ks" | "wm" | "pcr" | "cr" | "stockham"

    @property
    def key(self) -> str:
        return f"{self.op}:{self.variant or 'default'}:n{self.n}:b{self.batch}:{self.dtype}"

    def canonical(self) -> "Workload":
        """Canonical form: int dims, batch >= 1, dtype as a numpy name.

        Every config-resolution entry point funnels through this so that
        e.g. ``dtype=jnp.float32`` and ``dtype="float32"`` hit the same DB
        and cache keys.
        """
        dtype = self.dtype if isinstance(self.dtype, str) \
            else np.dtype(self.dtype).name
        n, batch = int(self.n), max(int(self.batch), 1)
        if dtype == self.dtype and n == self.n and batch == self.batch:
            return self
        return dataclasses.replace(self, n=n, batch=batch, dtype=dtype)


@dataclasses.dataclass
class SearchSpace:
    """Performance Parameters `B` + constraints for one workload."""

    workload: Workload
    params: Sequence[ParamSpec]
    constraints: Sequence[Callable[[Config, Workload], bool]] = ()
    # the hardware profile whose limits bound this space (validity
    # constraints capture it at build time; consumers read it for
    # budgets/geometry). Defaults to the process-wide active profile.
    spec: HardwareProfile = dataclasses.field(default_factory=active_profile)
    # memoized enumerate_valid(): every consumer (sweep, analytical rank,
    # strategies, featurizer) re-enumerates the same space; the constraint
    # closures are the expensive part, not the product itself
    _valid_cache: Optional[List[Config]] = dataclasses.field(
        default=None, repr=False, compare=False)

    def param(self, name: str) -> ParamSpec:
        for p in self.params:
            if p.name == name:
                return p
        raise KeyError(name)

    def is_valid(self, cfg: Config) -> bool:
        for p in self.params:
            if cfg.get(p.name) not in p.domain:
                return False
        return all(c(cfg, self.workload) for c in self.constraints)

    def enumerate_all(self) -> List[Config]:
        names = [p.name for p in self.params]
        out = []
        for values in itertools.product(*[p.domain for p in self.params]):
            out.append(dict(zip(names, values)))
        return out

    def enumerate_valid(self) -> List[Config]:
        if self._valid_cache is None:
            self._valid_cache = [c for c in self.enumerate_all()
                                 if self.is_valid(c)]
        # fresh list each call — callers sort/slice it (the config dicts
        # themselves are treated read-only everywhere)
        return list(self._valid_cache)

    # --- encoding for the GP surrogate: log2-normalized coordinates ---
    def encode(self, cfg: Config) -> List[float]:
        coords = []
        for p in self.params:
            dom = p.domain
            if len(dom) == 1:
                coords.append(0.0)
                continue
            lo, hi = math.log2(dom[0] + 1), math.log2(dom[-1] + 1)
            coords.append((math.log2(cfg[p.name] + 1) - lo) / (hi - lo))
        return coords

    def size(self) -> int:
        return len(self.enumerate_valid())


# ---------------------------------------------------------------------------
# Constraint builders shared by the kernel spaces
# ---------------------------------------------------------------------------

def plan_fits(spec: Optional[HardwareProfile] = None):
    """Every launch of the config's StagePlan fits the scoped VMEM the
    kernels are compiled under (``vmem_budget``).

    The plan counts the double-buffered pipeline blocks, the scratch and
    the temporaries of the in-kernel fold, so a config this admits is one
    Mosaic accepts — the tuners can never choose a kernel that does not
    compile.
    """
    spec = spec if spec is not None else active_profile()

    def check(cfg: Config, wl: Workload) -> bool:
        # late import: the planner builds on this module
        from repro.kernels.blocks.plan import plan_for
        return plan_for(wl, cfg, profile=spec).vmem_bytes <= spec.vmem_budget

    return check


def tile_divides_n():
    def check(cfg: Config, wl: Workload) -> bool:
        tile_n = cfg.get("tile_n", wl.n)
        return tile_n <= wl.n and wl.n % tile_n == 0

    return check


def rows_divide_batch():
    def check(cfg: Config, wl: Workload) -> bool:
        rows = cfg.get("rows_per_program", 1)
        return rows <= max(wl.batch, 1) and max(wl.batch, 1) % rows == 0

    return check


def radix_compatible():
    """radix^k must reach tile_n, and unroll must cover the radix fan-in."""

    def check(cfg: Config, wl: Workload) -> bool:
        r = cfg.get("radix", 2)
        tile_n = cfg.get("tile_n", wl.n)
        if r > tile_n:
            return False
        # tile_n must be a power of the radix for a uniform circuit; mixed
        # radix (paper Fig 5's jagged WM line) is valid but penalized by the
        # objective, not the space.
        k = round(math.log(tile_n, r))
        return r ** k == tile_n or (r ** k) * 2 == tile_n or tile_n % r == 0

    return check


def in_register_rule(spec: Optional[HardwareProfile] = None):
    """`in_register` (shuffle analogue) only when one problem row fits a VREG
    tile region: n <= 8 lanes*sublanes worth of data we keep resident."""
    spec = spec if spec is not None else active_profile()

    def check(cfg: Config, wl: Workload) -> bool:
        if not cfg.get("in_register", 0):
            return True
        return wl.n <= spec.lane_count * spec.sublane_count

    return check


# ---------------------------------------------------------------------------
# Per-operation space declarations (paper Table I, adapted per DESIGN.md §2)
# ---------------------------------------------------------------------------

def scan_space(wl: Workload,
               spec: Optional[HardwareProfile] = None) -> SearchSpace:
    spec = spec if spec is not None else active_profile()
    max_rows = floor_pow2(min(512, max(wl.batch, 1)))
    # variant-aware knob pruning: the linrec kernel's fold order is fixed
    # by the (a, b) composition algebra, so sweeping `unroll` there only
    # duplicated configs (inflated exhaustive sweeps, label noise in the
    # ML dataset)
    unroll_dom = (1,) if wl.variant == "linrec" else (1, 2, 4, 8)
    params = [
        ParamSpec("tile_n", tuple(v for v in pow2_range(128, max(wl.n, 128)) if v <= wl.n) or (wl.n,)),
        ParamSpec("rows_per_program", pow2_range(1, max_rows)),
        ParamSpec("radix", (2, 4, 8)),          # tree fan-in per level
        ParamSpec("unroll", unroll_dom),        # node-ops per VPU step
        ParamSpec("in_register", (0, 1)),
    ]
    return SearchSpace(
        wl,
        params,
        constraints=(
            tile_divides_n(),
            rows_divide_batch(),
            radix_compatible(),
            in_register_rule(spec),
            plan_fits(spec),
        ),
        spec=spec,
    )


def linrec_space(wl: Workload,
                 spec: Optional[HardwareProfile] = None) -> SearchSpace:
    """Scan space with the linrec-dead knobs pruned (rglru & friends)."""
    return scan_space(dataclasses.replace(wl, variant=wl.variant or "linrec"),
                      spec)


def tridiag_space(wl: Workload,
                  spec: Optional[HardwareProfile] = None) -> SearchSpace:
    spec = spec if spec is not None else active_profile()
    if wl.variant in ("cr", "lf", "thomas"):
        # these variants consume no tuned knobs at all (XLA-fused solves);
        # a singleton space keeps sweeps/datasets free of duplicate configs
        params = [
            ParamSpec("tile_n", (wl.n,)),
            ParamSpec("rows_per_program", (1,)),
            ParamSpec("radix", (2,)),
            ParamSpec("unroll", (1,)),
            ParamSpec("in_register", (0,)),
        ]
        return SearchSpace(wl, params, constraints=(plan_fits(spec),),
                           spec=spec)
    max_rows = floor_pow2(min(256, max(wl.batch, 1)))
    radix_dom = (2, 4, 8) if wl.variant == "wm" else (2,)  # paper: only WM retunes r
    # wm runs as an XLA chunked prefix: rows/unroll/in_register shape
    # nothing it executes, so only the radix (-> chunk) is swept
    rows_dom = (1,) if wl.variant == "wm" else pow2_range(1, max_rows)
    unroll_dom = (1,) if wl.variant == "wm" else (1, 2, 4)
    in_reg_dom = (0,) if wl.variant == "wm" else (0, 1)
    params = [
        ParamSpec("tile_n", (wl.n,)),           # whole system stays resident
        ParamSpec("rows_per_program", rows_dom),
        ParamSpec("radix", radix_dom),
        ParamSpec("unroll", unroll_dom),
        ParamSpec("in_register", in_reg_dom),
    ]
    return SearchSpace(
        wl,
        params,
        constraints=(
            rows_divide_batch(),
            radix_compatible(),
            in_register_rule(spec),
            plan_fits(spec),
        ),
        spec=spec,
    )


def fft_space(wl: Workload,
              spec: Optional[HardwareProfile] = None) -> SearchSpace:
    spec = spec if spec is not None else active_profile()
    max_rows = floor_pow2(min(256, max(wl.batch, 1)))
    params = [
        ParamSpec("tile_n", (wl.n,)),
        ParamSpec("rows_per_program", pow2_range(1, max_rows)),
        ParamSpec("radix", (2, 4, 8, 16)),      # Stockham radix (paper: {2,4,8,16})
        ParamSpec("unroll", (1, 2, 4)),
        ParamSpec("in_register", (0,)),          # paper: no shuffle for FFT
    ]
    return SearchSpace(
        wl,
        params,
        constraints=(rows_divide_batch(), radix_compatible(),
                     plan_fits(spec)),
        spec=spec,
    )


def large_fft_space(wl: Workload, max_tile: int = 4096,
                    spec: Optional[HardwareProfile] = None) -> SearchSpace:
    """Multi-pass FFT (paper §IV-C): N exceeds the on-chip tile -> m passes.

    The space covers (tile_n per pass, radix per pass, rows). tile_n here is
    the per-pass working-set S; m = ceil(log(N)/log(S)).
    """
    spec = spec if spec is not None else active_profile()
    max_rows = floor_pow2(min(64, max(wl.batch, 1)))
    tiles = tuple(v for v in pow2_range(256, max_tile))
    params = [
        ParamSpec("tile_n", tiles),
        ParamSpec("rows_per_program", pow2_range(1, max_rows)),
        ParamSpec("radix", (2, 4, 8, 16)),
        ParamSpec("unroll", (1, 2, 4)),
        ParamSpec("in_register", (0,)),
    ]

    def tile_le_n(cfg: Config, w: Workload) -> bool:
        return cfg["tile_n"] <= w.n

    return SearchSpace(
        wl,
        params,
        constraints=(rows_divide_batch(), radix_compatible(), tile_le_n,
                     plan_fits(spec)),
        spec=spec,
    )


def attention_space(wl: Workload,
                    spec: Optional[HardwareProfile] = None) -> SearchSpace:
    """Flash-attention block sizes (beyond-paper application of the method).

    wl.n = kv sequence length; wl.batch = #(batch*heads) rows.
    """
    spec = spec if spec is not None else active_profile()
    # no `unroll` knob: the flash kernel's inner loop is the block_k walk —
    # there is nothing to unroll independently of block_k, so sweeping it
    # only duplicated configs (the repro.analysis dead-knob detector flags
    # exactly this class; same pruning as linrec's unroll)
    params = [
        ParamSpec("block_q", (128, 256, 512, 1024)),
        ParamSpec("block_k", (128, 256, 512, 1024, 2048)),
        ParamSpec("rows_per_program", (1,)),
        ParamSpec("radix", (2,)),
        ParamSpec("in_register", (0,)),
    ]

    def blocks_within_n(cfg: Config, w: Workload) -> bool:
        return cfg["block_k"] <= w.n and cfg["block_q"] <= w.n

    return SearchSpace(wl, params,
                       constraints=(blocks_within_n, plan_fits(spec)),
                       spec=spec)


def matmul_space(wl: Workload,
                 spec: Optional[HardwareProfile] = None) -> SearchSpace:
    """Tiled matmul (M=batch, K=N=wl.n simplification for tuning demos)."""
    spec = spec if spec is not None else active_profile()
    params = [
        ParamSpec("block_m", (128, 256, 512)),
        ParamSpec("block_n", (128, 256, 512, 1024)),
        ParamSpec("block_k", (128, 256, 512, 1024, 2048)),
    ]

    def fits(cfg: Config, w: Workload) -> bool:
        eb = 2
        foot = (cfg["block_m"] * cfg["block_k"] + cfg["block_k"] * cfg["block_n"]) * eb
        foot += cfg["block_m"] * cfg["block_n"] * 4
        return foot * 2 <= spec.vmem_budget

    return SearchSpace(wl, params, constraints=(fits,), spec=spec)


_SPACE_BUILDERS: Dict[str, Callable[[Workload], SearchSpace]] = {
    "scan": scan_space,
    "tridiag": tridiag_space,
    "fft": fft_space,
    "large_fft": large_fft_space,
    "ssd": scan_space,        # the SSD inter-chunk scan shares the scan space
    "rglru": linrec_space,    # rglru IS a linrec: dead unroll knob pruned
    "attention": attention_space,
    "matmul": matmul_space,
}


def build_space(wl: Workload,
                profile: Optional[HardwareProfile] = None, *,
                spec: Optional[HardwareProfile] = None) -> SearchSpace:
    """Search space for ``wl`` bounded by ``profile`` (default: active
    profile).  ``spec=`` is a deprecated alias for ``profile=`` (the name
    the pre-policy API used — see docs/hardware.md).

    Externally registered builders that predate the profile layer may not
    take a ``spec`` argument; they are called without one and keep their
    own bounds.
    """
    if spec is not None:
        warnings.warn("build_space(spec=...) is deprecated; pass profile=...",
                      DeprecationWarning, stacklevel=2)
        if profile is None:
            profile = spec
    try:
        builder = _SPACE_BUILDERS[wl.op]
    except KeyError:
        raise KeyError(f"no search space registered for op={wl.op!r}") from None
    if profile is None:
        return builder(wl)
    try:
        params = inspect.signature(builder).parameters
        accepts_spec = "spec" in params or any(
            p.kind is p.VAR_KEYWORD for p in params.values())
    except (TypeError, ValueError):
        accepts_spec = False
    return builder(wl, spec=profile) if accepts_spec else builder(wl)


def register_space(op: str, builder: Callable[[Workload], SearchSpace]) -> None:
    _SPACE_BUILDERS[op] = builder


# ---------------------------------------------------------------------------
# Shared config normalization (launch-geometry fitting)
# ---------------------------------------------------------------------------
# Tuned configs are stored for the workload they were searched on; at launch
# time the knobs must still divide the actual array dims (a stored tile of
# 512 against n=384, say). Every kernel family used to carry its own copy of
# this halving descent; it lives here now and per-op normalizers in
# kernels/*/ops.py compose it.

def fit_block(value: int, dim: int) -> int:
    """Largest v <= min(value, dim) reachable by halving with dim % v == 0."""
    v = int(max(min(value, dim), 1))
    while dim % v:
        v //= 2
    return max(v, 1)


def normalize_config(cfg: Mapping[str, int], wl: Workload,
                     dims: Optional[Mapping[str, int]] = None) -> Config:
    """Generic normalizer: snap row/tile knobs to the workload dims.

    Per-op normalizers registered via ``repro.tuning.tuned_kernel`` take
    precedence; this fallback handles any op without one.
    """
    out = dict(cfg)
    if "rows_per_program" in out:
        out["rows_per_program"] = fit_block(out["rows_per_program"],
                                            max(wl.batch, 1))
    if "tile_n" in out:
        out["tile_n"] = fit_block(out["tile_n"], wl.n)
    return out
