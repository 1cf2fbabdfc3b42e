"""Whole calls' share of the chip's peak, in percent.

Each call's least time is the larger of its algorithmic operations over
the bf16 peak and its input plus output bytes over the HBM bandwidth,
from shapes alone (``harness.counts``), whatever implements the op.  The
share is their sum over the calls issued in the traced window, divided
by the window.
"""
from harness.counts import least_time_s


def read(ctx):
    calls = ctx.readings.get("calls")
    window = ctx.trace.window_s
    if not calls or window <= 0:
        return None
    least = sum(least_time_s(op, n, batch, ctx.peaks)
                for op, n, batch in calls)
    return 100.0 * least / window
