"""The device check and the table of published peaks.

The benchmark runs on a TPU or not at all: no CPU fallback, and a device
kind that is not in :data:`PEAKS` is an error rather than a default.
"""
from __future__ import annotations

from typing import Dict

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}
PEAKS_SOURCE = "Google Cloud documentation, TPU v5e"


class DeviceError(RuntimeError):
    """The machine cannot run the cell: no TPU, too few chips, or a chip
    whose peaks are not in the table."""


def peaks_for(kind: str) -> Dict[str, float]:
    try:
        return PEAKS[kind]
    except KeyError:
        raise DeviceError(f"no published peaks for device kind {kind!r} "
                          f"(known: {sorted(PEAKS)})") from None


def check_devices(devices, chips: int) -> Dict[str, object]:
    """Validate ``devices`` (as ``jax.devices()`` returns them) for a cell
    that needs ``chips`` chips; returns the result's ``device`` block."""
    if not devices:
        raise DeviceError("JAX reports no devices")
    first = devices[0]
    if first.platform != "tpu":
        raise DeviceError(f"no TPU: JAX's first device is {first.platform!r}")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX sees "
                          f"{len(devices)}")
    peaks_for(first.device_kind)
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
