"""Algorithmic operation and byte counts, from shapes alone.

They are the same whatever implements the op: bytes are the input plus
the output read and written once, operations are what the algorithm
needs (the FFT's 5 N log2 N, the linear counts of the scans and of the
Thomas algorithm).  Recomputed or padded work never counts.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

F32 = 4
C64 = 8


def op_counts(op: str, n: int, batch: int) -> Tuple[float, float]:
    """(flops, bytes) of one call of ``op`` on ``batch`` rows of ``n``."""
    elems = float(n) * batch
    if op in ("prefix_sum.ks", "prefix_sum.lf"):
        return elems, 2 * F32 * elems                  # x in, y out
    if op == "linear_recurrence":
        return 2 * elems, 3 * F32 * elems              # a, b in, h out
    if op == "fft":
        return 5.0 * n * math.log2(n) * batch, 2 * C64 * elems
    if op == "tridiag.pcr":
        # Thomas: 6 flops forward and 2 back per unknown
        return 8 * elems, 5 * F32 * elems              # a, b, c, d in, x out
    raise KeyError(f"no counts for op {op!r}")


def least_time_s(op: str, n: int, batch: int,
                 peaks: Dict[str, float]) -> float:
    """The roofline's least time for one call: the larger of operations
    over peak bf16 FLOP/s and bytes over HBM bandwidth."""
    flops, nbytes = op_counts(op, n, batch)
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def mamba2_dims(cfg: Dict) -> Dict[str, int]:
    d_inner = cfg["expand"] * cfg["d_model"]
    return {"d_model": cfg["d_model"], "d_inner": d_inner,
            "heads": d_inner // cfg["headdim"], "state": cfg["d_state"],
            "headdim": cfg["headdim"], "conv": cfg["d_conv"],
            "layers": cfg["n_layer"], "vocab": cfg["vocab_rows"],
            "proj": 2 * d_inner + 2 * cfg["d_state"]
            + d_inner // cfg["headdim"]}


def mamba2_flops_per_token(cfg: Dict, logits: bool = True) -> float:
    """Projections, the SSD state update and readout, and (for a token
    whose logits are needed) the tied unembedding; 2 flops a MAC."""
    d = mamba2_dims(cfg)
    proj = d["d_model"] * d["proj"] + d["d_inner"] * d["d_model"]
    state = d["heads"] * d["state"] * d["headdim"]
    # h = a*h + B x^T (3 flops an element), y = C . h (2 flops an element)
    per_layer = 2 * proj + 5 * state
    total = d["layers"] * per_layer
    if logits:
        total += 2 * d["d_model"] * d["vocab"]
    return float(total)


def mamba2_state_bytes_per_lane(cfg: Dict) -> int:
    """f32 SSD state plus f32 conv window, every layer."""
    d = mamba2_dims(cfg)
    ssd = d["heads"] * d["state"] * d["headdim"] * F32
    conv = (d["conv"] - 1) * (d["d_inner"] + 2 * d["state"]) * F32
    return d["layers"] * (ssd + conv)
