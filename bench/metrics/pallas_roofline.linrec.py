"""``pallas_roofline`` of the ``scan_linrec`` kernels alone, in percent.

The program names the kernels of ``linear_recurrence`` ``scan_linrec``
(a multi-pass launch adds a stage suffix).  The share is their operand
plus result bytes over the HBM bandwidth, over their device time.
"""
from harness.named import family_roofline_pct


def read(ctx):
    return family_roofline_pct(ctx.trace, ctx.peaks, "scan_linrec")
