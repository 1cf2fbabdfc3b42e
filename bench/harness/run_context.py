"""What a driver gets from the harness, and what it hands back."""
from __future__ import annotations

import contextlib
import dataclasses
import shutil
import sys
import tempfile
from typing import Any, Dict, List, Optional

from harness.cells import Cell


class Tracer:
    """The profiler, on only in a ``--trace 1`` run.  Spans are no-ops
    otherwise, so the end-to-end runs carry no tracing cost."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self._dir: Optional[str] = None
        self._window = None
        self.reduced = None

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        """Start the profiler and open the ``bench.window`` span, whose
        extent is the traced window."""
        if not self.enabled or self._dir is not None:
            return
        import jax
        from trace_reduce import WINDOW_SPAN
        self._dir = tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=options)
        self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()

    def stop(self) -> None:
        """Close the window span and stop the profiler."""
        if self._window is None:
            return
        import jax
        self._window.__exit__(None, None, None)
        self._window = None
        jax.profiler.stop_trace()

    def reduce(self) -> None:
        """Reduce the recorded trace (after the window); the raw trace is
        deleted once reduced."""
        if self._dir is None:
            return
        from trace_reduce import reduce_trace
        try:
            self.reduced = reduce_trace(self._dir)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None


@dataclasses.dataclass
class RunContext:
    cell: Cell
    seed: int
    seconds: float
    t_start: float              # perf_counter() at process start
    devices: List[Any]
    peaks: Dict[str, float]
    tracer: Tracer
    control: bool = False       # also read the control (calibration only)

    @staticmethod
    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Outcome:
    """A driver's result: the end-to-end values it measured, the inputs
    of the per-layer readers, and the numbers compared for ``correct``."""
    attempted: int
    failed: int
    setup_s: float
    end_to_end: Dict[str, float]
    check: Dict[str, Dict[str, float]]
    memory_peak_bytes: int
    readings: Dict[str, Any] = dataclasses.field(default_factory=dict)
    controls: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return self.failed == 0 and bool(self.check) and all(
            c["value"] == c["value"] and c["value"] <= c["limit"]
            for c in self.check.values())


@dataclasses.dataclass
class MetricContext:
    """What a per-layer reader (``bench/metrics/<name>.py``) reads."""
    trace: Any                  # trace_reduce.Reduced
    readings: Dict[str, Any]
    peaks: Dict[str, float]
    cell: Cell

