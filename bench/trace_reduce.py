"""Reduces a profiler trace (``.xplane.pb``) to what the metrics read.

It reads the file with ``jax.profiler.ProfileData`` and nothing else:

* device planes ``/device:TPU:<i>``: the ``XLA Ops`` line (one event per
  HLO instruction run, named by its HLO text) and the ``XLA Modules``
  line (one event per program run, named ``<jitted name>(<hash>)``);
* host planes: the harness's own ``TraceAnnotation`` spans.

Host and device events share one clock in the file.  The traced window
is the host span ``bench.window`` when there is one, else the extent of
the device events.  All times below are seconds, clipped to the window.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
# device gaps shorter than this lie between the ops of one program and
# are not attributed to a host span
SHORT_GAP_S = 20e-6

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
                "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}
_SHAPE = re.compile(r"\b(" + "|".join(sorted(_DTYPE_BYTES, key=len,
                                             reverse=True))
                    + r")\[([0-9,]*)\]")
_PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
# HLO ops whose events enclose the events of the ops they run
_CONTAINERS = ("while", "conditional", "call")


def shape_bytes(text: str) -> int:
    """Bytes of every array shape (``f32[8,128]``) written in ``text``."""
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        count = 1
        for d in filter(None, dims.split(",")):
            count *= int(d)
        total += count * _DTYPE_BYTES[dtype]
    return total


def custom_call_bytes(hlo: str) -> int:
    """Operand plus result bytes of one ``custom-call`` HLO instruction."""
    head, sep, rest = hlo.partition(" custom-call(")
    if not sep:
        return 0
    result = head.split(" = ", 1)[-1]
    depth, end = 1, len(rest)
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            end = i
            break
    return shape_bytes(result) + shape_bytes(rest[:end])


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def module_name(event_name: str) -> str:
    """``jit_step(1505...)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0]


@dataclasses.dataclass
class Op:
    device: int
    module: str
    hlo: str
    start: float
    dur: float

    @property
    def is_pallas(self) -> bool:
        return _PALLAS_TARGET in self.hlo


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]
    devices: List[int]
    busy: Dict[int, List[Tuple[float, float]]]
    ops: List[Op]
    modules: List[Tuple[int, str, float, float]]
    spans: List[Tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Seconds with an op running, averaged over the devices used."""
        if not self.devices:
            return 0.0
        return sum(sum(e - s for s, e in self.busy[d])
                   for d in self.devices) / len(self.devices)

    def pallas_calls(self) -> List[Tuple[Op, int]]:
        """Every Pallas kernel run, with its operand plus result bytes."""
        return [(op, custom_call_bytes(op.hlo)) for op in self.ops
                if op.is_pallas]

    def module_time(self, name: str) -> Tuple[float, int]:
        """(device seconds, runs) of the programs jitted as ``name``."""
        runs = [dur for _, mod, _, dur in self.modules if mod == name]
        return sum(runs), len(runs)

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` ops with the most device time, as [module:op, s];
        control-flow ops that contain others are left out."""
        totals: Dict[str, float] = collections.defaultdict(float)
        for op in self.ops:
            if op_name(op.hlo).startswith(_CONTAINERS):
                continue
            totals[f"{op.module}:{op_name(op.hlo)}"] += op.dur
        return [[n, t] for n, t in sorted(totals.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def idle_by_span(self, names: Optional[Iterable[str]] = None
                     ) -> Dict[str, float]:
        """Idle device seconds, each gap attributed to the innermost host
        span (among ``names``) open during it; short gaps between the ops
        of a program go to ``between ops``, uncovered time to ``no span``."""
        wanted = None if names is None else set(names)
        spans = sorted((s for s in self.spans
                        if s[0] != WINDOW_SPAN
                        and (wanted is None or s[0] in wanted)),
                       key=lambda s: s[1])
        starts = [s[1] for s in spans]
        longest = max((e - b for _, b, e in spans), default=0.0)
        out: Dict[str, float] = collections.defaultdict(float)
        for device in self.devices:
            for g0, g1 in _gaps(self.busy[device], self.window):
                if g1 - g0 < SHORT_GAP_S:
                    out["between ops"] += (g1 - g0) / len(self.devices)
                    continue
                lo = bisect.bisect_left(starts, g0 - longest)
                hi = bisect.bisect_right(starts, g1)
                cover = [s for s in spans[lo:hi] if s[2] > g0 and s[1] < g1]
                cuts = sorted({g0, g1, *(max(s[1], g0) for s in cover),
                               *(min(s[2], g1) for s in cover)})
                for a, b in zip(cuts, cuts[1:]):
                    mid = 0.5 * (a + b)
                    inner = [s for s in cover if s[1] <= mid < s[2]]
                    name = (max(inner, key=lambda s: s[1])[0] if inner
                            else "no span")
                    out[name] += (b - a) / len(self.devices)
        return dict(out)


def _union(intervals: Sequence[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _gaps(busy: Sequence[Tuple[float, float]], window: Tuple[float, float]
          ) -> List[Tuple[float, float]]:
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_trace(path: str) -> Reduced:
    """Reduce the trace at ``path`` (an ``.xplane.pb`` or a directory
    holding one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    raw_ops: List[Tuple[int, str, float, float]] = []
    raw_modules: List[Tuple[int, str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            device = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    raw_ops.extend((device, e.name, e.start_ns * 1e-9,
                                    e.duration_ns * 1e-9)
                                   for e in line.events)
                elif line.name == "XLA Modules":
                    raw_modules.extend((device, module_name(e.name),
                                        e.start_ns * 1e-9,
                                        e.duration_ns * 1e-9)
                                       for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith("$") or " " in name or ":" in name:
                        continue    # python tracer and runtime internals
                    spans.append((name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9))
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if windows:
        window = (windows[0][1], windows[0][2])
    elif raw_ops:
        window = (min(o[2] for o in raw_ops),
                  max(o[2] + o[3] for o in raw_ops))
    else:
        window = (0.0, 0.0)
    lo, hi = window

    def clip(start: float, dur: float) -> Tuple[float, float]:
        s, e = max(start, lo), min(start + dur, hi)
        return s, max(e - s, 0.0)

    modules = []
    for device, name, start, dur in raw_modules:
        s, d = clip(start, dur)
        if d > 0:
            modules.append((device, name, s, d))
    mods_by_dev: Dict[int, List[Tuple[float, float, str]]] = \
        collections.defaultdict(list)
    for device, name, start, dur in raw_modules:
        mods_by_dev[device].append((start, start + dur, name))
    for v in mods_by_dev.values():
        v.sort()
    ops: List[Op] = []
    for device, hlo, start, dur in raw_ops:
        s, d = clip(start, dur)
        if d <= 0:
            continue
        mods = mods_by_dev.get(device, [])
        i = bisect.bisect_right(mods, (start, float("inf"), "")) - 1
        module = mods[i][2] if i >= 0 and mods[i][1] >= start else "?"
        ops.append(Op(device, module, hlo, s, d))
    devices = sorted({op.device for op in ops})
    busy = {d: _union([(op.start, op.start + op.dur) for op in ops
                       if op.device == d]) for d in devices}
    in_window = [s for s in spans if s[2] > lo and s[1] < hi]
    return Reduced(window=window, devices=devices, busy=busy, ops=ops,
                   modules=modules, spans=in_window)
