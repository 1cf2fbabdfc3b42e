"""What the program names itself, read off a reduced trace.

The program marks its own work: host spans (``serve.step``,
``repro.compile``, ...) through ``repro.obs``, and a name on each Pallas
launch that becomes the kernel's HLO instruction name (``scan_ks.1``,
``scan_linrec_chunk.2``).  A program that predates a mark has none of
it; the readers built on these helpers then return None.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from trace_reduce import op_name


def spans(trace, name: str) -> List[Tuple[float, float]]:
    """(start, end) of every span ``name`` that overlaps the window,
    clipped to it."""
    lo, hi = trace.window
    return [(max(s, lo), min(e, hi)) for n, s, e in trace.spans if n == name]


def span_seconds(trace, name: str) -> float:
    return sum(e - s for s, e in spans(trace, name))


def counts_compiles() -> bool:
    """Whether the program under test counts its compiles (it emits a
    ``repro.compile`` span for each one)."""
    try:
        from repro import obs
    except ImportError:
        return False
    return obs.watching()


# the stages of a multi-pass launch, as the program suffixes their names
STAGES = ("chunk", "carry", "apply")


def in_family(kernel: str, family: str) -> bool:
    """``scan_ks.1`` and ``scan_ks_chunk.1`` are of family ``scan_ks``."""
    base = kernel.split(".", 1)[0]
    return base == family or base in {f"{family}_{s}" for s in STAGES}


# prefix_sum's variants, which compile to one program where their
# configs agree (see family_roofline_pct)
TWINS = {"scan_ks": "scan_lf", "scan_lf": "scan_ks"}


def _launch_key(hlo: str, family: str) -> Tuple[str, str]:
    """(stage suffix, result shape) of one launch of ``family``."""
    name, _, rest = hlo.partition(" = ")
    stage = op_name(name).split(".", 1)[0][len(family):]
    return stage, rest.split(" ", 1)[0]


def family_roofline_pct(trace, peaks, family: str) -> Optional[float]:
    """``pallas_roofline``'s share (operand plus result bytes over the
    HBM bandwidth, over device time) for the Pallas kernels of one
    family; None where the trace has none of them.

    A TPU runtime loads each distinct compiled program once, and the
    trace labels every run of it with the name it was loaded under.  So
    where two variants compile to the same kernel (prefix_sum's ks and
    lf with the same config), all runs of that launch carry one of the
    two names.  A launch (stage and shape) that shows under one twin's
    name only is taken as shared, half to each: the scan cells call both
    variants at every size equally often, and the runs are of one
    program.  The per-family shares, weighted by their device time, then
    give ``pallas_roofline``.
    """
    twin = TWINS.get(family)
    own, twins = [], []
    for op, nbytes in trace.pallas_calls():
        name = op_name(op.hlo)
        if in_family(name, family):
            own.append((op, nbytes, _launch_key(op.hlo, family)))
        elif twin is not None and in_family(name, twin):
            twins.append((op, nbytes, _launch_key(op.hlo, twin)))
    own_keys = {key for _, _, key in own}
    twin_keys = {key for _, _, key in twins}
    runs = [(op, nbytes, 1.0 if twin is None or key in twin_keys else 0.5)
            for op, nbytes, key in own]
    runs += [(op, nbytes, 0.5) for op, nbytes, key in twins
             if key not in own_keys]
    busy = sum(w * op.dur for op, _, w in runs)
    if not runs or busy <= 0:
        return None
    least = sum(w * nbytes for _, nbytes, w in runs)
    return 100.0 * least / peaks["hbm_bytes_per_s"] / busy
