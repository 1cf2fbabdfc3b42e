"""Hardware profiles — parametric machine models as data, not code.

The paper's headline claim is performance *portability*: the same
analytical and ML tuning methodologies retarget from a server GPU to an
embedded Jetson by swapping the machine model underneath (PAPER.md
§III–V).  This module is that swap point.  A :class:`HardwareProfile` is
a frozen dataclass of architectural constants (peak rates, memory
hierarchy, tiling geometry, launch/DMA/sync latencies, mesh geometry — a
strict superset of the historical ``TpuSpec``) plus the machine-model
response curves evaluated against it (lane/sublane utilization, DMA
bandwidth ramp, ILP issue factor).

Every layer that used to import ``hw.tpu.V5E`` directly now carries a
profile: ``SearchSpace`` validity bounds, ``StagePlan`` VMEM/pass
accounting, the cost-model objective, ``TunerSession`` (profile names key
TuningDB entries and sweep-journal signatures), and the ML featurizer
(device columns, so one forest can pool rows across profiles).

Registry
--------
Three concrete profiles ship (see docs/hardware.md for the field
glossary and how to add a device):

* ``tpu_v5e``   — the historical constants; costs pinned bit for bit by a
  fixture test (its scan-family records recaptured when the per-stage
  barrier became ``stage_sync_s`` = 0);
* ``gpu_sm``    — a CUDA-core/SMEM-shaped profile in the spirit of the
  paper's GM20B table, with the Pallas Triton backend's geometry (warp
  lanes, tensor-core tile, kernel-relaunch sync);
* ``cpu_interpret`` — the pallas interpret-mode host, so the profile
  layer is exercisable in CI without accelerators.

``active_profile()`` resolves ``$REPRO_HW_PROFILE`` when it is set,
which is how the CI profile matrix retargets the whole stack without
touching call sites.  Otherwise a process whose JAX backend is a TPU gets
the profile registered for its ``device_kind`` (an unknown kind raises),
and every other process models ``tpu_v5e``.
"""
from __future__ import annotations

import dataclasses
import math
import os
import sys
from typing import Dict, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    """One device's architectural constants (the paper's Table of limits).

    Field defaults ARE the TPU v5e machine model — ``HardwareProfile()``
    carries the historical ``TpuSpec()`` constants, plus
    ``stage_sync_s`` = 0: a TPU core runs a kernel body's stages without
    a barrier between them.
    """

    name: str = "tpu_v5e"
    # --- identity ---
    kind: str = "tpu"                     # "tpu" | "gpu" | "cpu"
    backend: str = "pallas_tpu"           # "pallas_tpu" | "pallas_triton"
    #                                       | "interpret"
    # --- per-chip peak rates ---
    peak_bf16_flops: float = 197e12       # FLOP/s per chip, matrix-unit bf16
    peak_f32_flops: float = 98.5e12       # matrix-unit f32
    peak_vpu_flops: float = 3.2e12        # vector/elementwise f32
    hbm_bandwidth: float = 819e9          # B/s per chip
    ici_link_bandwidth: float = 50e9      # B/s per interconnect link
    # --- memory hierarchy ---
    hbm_bytes: int = 16 * 2**30           # device memory per chip
    vmem_bytes: int = 128 * 2**20         # fast on-chip scratch pool
    vmem_budget: int = 32 * 2**20         # scoped VMEM every kernel compiles
    #                                       under (passed to Mosaic as
    #                                       vmem_limit_bytes) and the bound
    #                                       on each launch's planned VMEM
    # --- tiling geometry ---
    lane_count: int = 128                 # trailing vector dim (warp width
    #                                       on GPU, SIMD lanes on CPU)
    sublane_count: int = 8                # second-to-last vector dim
    mxu_dim: int = 128                    # matrix-unit edge (tensor-core
    #                                       tile on GPU)
    # --- pipeline model ---
    dma_latency_s: float = 2e-6           # per-block DMA issue latency
    kernel_launch_s: float = 5e-6         # fixed kernel-launch overhead
    pass_sync_s: float = 1.5e-6           # per-pass barrier/flush cost
    stage_sync_s: float = 0.0             # barrier between the stages of
    #                                       one kernel body (0: a program's
    #                                       stages are straight-line vector
    #                                       code, as on a TPU core)
    grid_step_s: float = 0.35e-6          # one step of a launch's grid
    #                                       pipeline on a TPU v5e: the
    #                                       ssd_carry launch at 64 x 8,192
    #                                       rows took 0.35-0.39 us more per
    #                                       added (row, chunk) step going
    #                                       from chunk 512 to 128 (PERF.md)
    dma_half_bytes: int = 64 * 2**10      # DMA ramp half-saturation point
    ilp_base: float = 0.55                # issue utilization at unroll=1
    ilp_slope: float = 0.15               # utilization gained per doubling
    # --- power model (energy = idle + compute-activity + data-movement) ---
    idle_w: float = 60.0                  # static draw while a kernel runs
    peak_compute_w: float = 140.0         # dynamic draw of busy compute units
    hbm_pj_per_byte: float = 150.0        # pJ per byte moved through HBM/DDR
    # --- mesh geometry ---
    chips_per_pod: int = 256


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

TPU_V5E = HardwareProfile()

GPU_SM = HardwareProfile(
    name="gpu_sm",
    kind="gpu",
    backend="pallas_triton",
    # Ampere-class server part (where the Pallas Triton backend runs),
    # with the CUDA-core/SMEM field shape of the paper's GM20B table
    peak_bf16_flops=165e12,               # tensor-core bf16
    peak_f32_flops=19.5e12,               # tensor-core tf32-ish
    peak_vpu_flops=19.5e12,               # CUDA-core f32
    hbm_bandwidth=1555e9,
    ici_link_bandwidth=600e9,             # NVLink
    hbm_bytes=40 * 2**30,
    vmem_bytes=40 * 2**20,                # L2 slice + SMEM pool
    vmem_budget=8 * 2**20,                # per-program staging budget (SMEM,
    #                                       register file and the L2 spill
    #                                       one program keeps resident)
    lane_count=32,                        # warp width
    sublane_count=4,                      # scheduler partitions per SM
    mxu_dim=16,                           # tensor-core tile edge
    dma_latency_s=1e-6,
    kernel_launch_s=8e-6,                 # CUDA launch overhead
    pass_sync_s=4e-6,                     # global barrier == kernel relaunch
    stage_sync_s=4e-6,                    # a stage's lane shifts cross
    #                                       warps, so stages sync; priced
    #                                       like the pass barrier
    grid_step_s=0.0,                      # a grid's programs are CTAs
    #                                       spread over the SMs, not steps
    #                                       of one core's pipeline
    dma_half_bytes=32 * 2**10,            # coalescing saturates earlier
    ilp_base=0.60,
    ilp_slope=0.10,
    idle_w=90.0,                          # server-part static draw
    peak_compute_w=310.0,                 # SM array at full issue
    hbm_pj_per_byte=180.0,                # HBM2e access energy
    chips_per_pod=8,                      # one NVLink island
)

CPU_INTERPRET = HardwareProfile(
    name="cpu_interpret",
    kind="cpu",
    backend="interpret",
    # pallas interpret mode on the CI host: AVX-ish vector unit, DDR
    # bandwidth, LLC as the "VMEM" analogue.  Exists so the profile layer
    # (spaces, plans, objectives, DB keying) is exercisable in CI without
    # accelerators — the constants are deliberately round.
    peak_bf16_flops=5e10,                 # bf16 emulated: slower than f32
    peak_f32_flops=1e11,
    peak_vpu_flops=1e11,
    hbm_bandwidth=40e9,
    ici_link_bandwidth=10e9,
    hbm_bytes=16 * 2**30,
    vmem_bytes=32 * 2**20,                # last-level cache
    vmem_budget=4 * 2**20,                # per-program resident working set
    lane_count=8,                         # AVX f32 lanes
    sublane_count=1,
    mxu_dim=8,
    dma_latency_s=1e-7,
    kernel_launch_s=50e-6,                # interpret-mode dispatch is slow
    pass_sync_s=1e-6,
    stage_sync_s=1e-6,                    # interpret mode dispatches each
    #                                       stage's ops from the host
    grid_step_s=1e-6,                     # interpret mode walks the grid
    #                                       as a host loop
    dma_half_bytes=4 * 2**10,             # streaming saturates quickly
    ilp_base=0.70,
    ilp_slope=0.10,
    idle_w=20.0,                          # host package at light load
    peak_compute_w=45.0,                  # vector units saturated
    hbm_pj_per_byte=400.0,                # DDR access is energy-expensive
    chips_per_pod=1,
)

_PROFILES: Dict[str, HardwareProfile] = {}


def register_profile(profile: HardwareProfile) -> HardwareProfile:
    """Add (or replace) a profile in the registry; returns it."""
    _PROFILES[profile.name] = profile
    return profile


def get_profile(name: str) -> HardwareProfile:
    try:
        return _PROFILES[name]
    except KeyError:
        raise ValueError(f"unknown hardware profile {name!r}; registered: "
                         f"{', '.join(profiles())}") from None


def profiles() -> Tuple[str, ...]:
    return tuple(sorted(_PROFILES))


# ``device_kind`` strings JAX reports for a chip -> the profile modeling it
_DEVICE_KINDS: Dict[str, str] = {
    "TPU v5 lite": "tpu_v5e",
}


def profile_for_device_kind(kind: str) -> HardwareProfile:
    """The profile of an attached TPU; a kind with no profile raises, since
    planning it as another chip would compile kernels for the wrong VMEM."""
    try:
        return get_profile(_DEVICE_KINDS[kind])
    except KeyError:
        raise ValueError(f"no hardware profile for TPU device_kind {kind!r}; "
                         f"known kinds: {', '.join(sorted(_DEVICE_KINDS))}"
                         ) from None


def _attached_tpu_kind() -> Optional[str]:
    """``device_kind`` of the TPU this process computes on, else None.

    A process that has not imported JAX runs no kernel, so it is never
    asked to initialize a backend here.
    """
    jax = sys.modules.get("jax")
    if jax is None or jax.default_backend() != "tpu":
        return None
    return jax.devices()[0].device_kind


def active_profile() -> HardwareProfile:
    """The process-wide default profile.

    ``$REPRO_HW_PROFILE`` wins when set; else an attached TPU's
    ``device_kind`` picks it (unknown kinds raise); else ``tpu_v5e``, the
    model CPU hosts plan and test against.  Read per call, so tests and the
    CI matrix can retarget the stack by environment without import-order
    traps.
    """
    name = os.environ.get("REPRO_HW_PROFILE")
    if name is not None:
        return get_profile(name)
    kind = _attached_tpu_kind()
    if kind is not None:
        return profile_for_device_kind(kind)
    return get_profile("tpu_v5e")


for _p in (TPU_V5E, GPU_SM, CPU_INTERPRET):
    register_profile(_p)


# ---------------------------------------------------------------------------
# Profile distance (cross-device transfer weighting)
# ---------------------------------------------------------------------------

# rate/geometry fields that shape a kernel's operating point; latencies are
# included because pass-heavy configs trade differently on launch-expensive
# devices
_DISTANCE_FIELDS = (
    "peak_vpu_flops", "peak_f32_flops", "hbm_bandwidth", "vmem_budget",
    "lane_count", "sublane_count", "mxu_dim", "kernel_launch_s",
    "pass_sync_s", "dma_half_bytes",
)


def profile_distance(a: HardwareProfile, b: HardwareProfile) -> float:
    """Mean |log2 ratio| over the rate/geometry fields; 0.0 iff identical.

    The transfer-seeding weight is ``exp(-distance)``: a device twice as
    fast in every dimension is "one octave away" and its journal evidence
    is discounted accordingly — close devices transfer almost fully,
    wildly different ones barely at all.
    """
    total = 0.0
    for field in _DISTANCE_FIELDS:
        va, vb = float(getattr(a, field)), float(getattr(b, field))
        total += abs(math.log2(max(va, 1e-30) / max(vb, 1e-30)))
    return total / len(_DISTANCE_FIELDS)


# ---------------------------------------------------------------------------
# Machine-model response curves
# ---------------------------------------------------------------------------
# Scalar and vectorized forms mirror each other element-for-element so
# batched and per-config evaluation agree to floating-point identity (the
# sweep engine depends on this).

def dtype_bytes(dtype) -> int:
    return np.dtype(dtype).itemsize


def effective_element_bytes(op: str, dtype) -> int:
    """Bytes one logical element of ``op`` moves through memory.

    Per-family multipliers over the raw dtype width: a tridiagonal element
    is an equation of 4 coefficients, an FFT element is an interleaved
    complex pair. The single source of truth for the analytical model, the
    cost objective, and the ML featurizer — which must agree, since the
    learned labels come from the cost model.
    """
    eb = dtype_bytes(dtype)
    if op == "tridiag":
        return 4 * eb
    if op in ("fft", "large_fft"):
        return 2 * eb
    return eb


def lane_utilization(trailing_dim: int,
                     spec: HardwareProfile = TPU_V5E) -> float:
    """Fraction of the lane dim that does useful work.

    The analogue of warp occupancy in the paper's guideline: a trailing
    dim of 96 on a 128-lane device wastes 25% of every vector issue; a
    trailing dim of 384 is three full tiles -> 1.0.
    """
    lanes = spec.lane_count
    if trailing_dim <= 0:
        return 0.0
    if trailing_dim >= lanes:
        full, rem = divmod(trailing_dim, lanes)
        used = full * lanes + rem
        tiles = full + (1 if rem else 0)
        return used / (tiles * lanes)
    return trailing_dim / lanes


def sublane_utilization(second_dim: int,
                        spec: HardwareProfile = TPU_V5E) -> float:
    sub = spec.sublane_count
    if second_dim <= 0:
        return 0.0
    if second_dim >= sub:
        full, rem = divmod(second_dim, sub)
        tiles = full + (1 if rem else 0)
        return second_dim / (tiles * sub)
    return second_dim / sub


def dma_efficiency(block_bytes: int,
                   spec: HardwareProfile = TPU_V5E) -> float:
    """Memory-bandwidth ramp: small transfers underutilize the system.

    Modeled as ``b / (b + b_half)`` with the half-saturation point a
    profile constant (64 KiB fits TPU DMA engines; GPUs coalesce earlier,
    CPUs stream-prefetch earlier still).
    """
    b_half = spec.dma_half_bytes
    return block_bytes / (block_bytes + b_half)


def ilp_factor(unroll: int, spec: HardwareProfile = TPU_V5E) -> float:
    """Issue-pipeline utilization vs in-kernel ILP (the paper's premise iii).

    One node-op per step leaves issue bubbles; saturates as unroll grows,
    with profile-specific base and slope.
    """
    return min(1.0, spec.ilp_base + spec.ilp_slope * math.log2(max(unroll, 1)))


# ---------------------------------------------------------------------------
# Vectorized counterparts (numpy arrays in, arrays out)
# ---------------------------------------------------------------------------

def lane_utilization_arr(trailing_dim, spec: HardwareProfile = TPU_V5E):
    t = np.asarray(trailing_dim, dtype=np.float64)
    lanes = float(spec.lane_count)
    full = np.floor(t / lanes)
    rem = t - full * lanes
    tiles = full + (rem > 0)
    multi = t / np.maximum(tiles * lanes, 1.0)
    out = np.where(t >= lanes, multi, t / lanes)
    return np.where(t <= 0, 0.0, out)


def sublane_utilization_arr(second_dim, spec: HardwareProfile = TPU_V5E):
    s = np.asarray(second_dim, dtype=np.float64)
    sub = float(spec.sublane_count)
    full = np.floor(s / sub)
    rem = s - full * sub
    tiles = full + (rem > 0)
    multi = s / np.maximum(tiles * sub, 1.0)
    out = np.where(s >= sub, multi, s / sub)
    return np.where(s <= 0, 0.0, out)


def dma_efficiency_arr(block_bytes, spec: HardwareProfile = TPU_V5E):
    b = np.trunc(np.asarray(block_bytes, dtype=np.float64))
    b_half = spec.dma_half_bytes
    return b / (b + b_half)


def ilp_factor_arr(unroll, spec: HardwareProfile = TPU_V5E):
    u = np.maximum(np.asarray(unroll, dtype=np.float64), 1.0)
    return np.minimum(1.0, spec.ilp_base + spec.ilp_slope * np.log2(u))
