"""``pallas_roofline`` of the ``scan_lf`` kernels alone, in percent.

The program names the kernels of ``prefix_sum`` (variant ``lf``)
``scan_lf`` (a multi-pass launch adds a stage suffix).  The share is
their operand plus result bytes over the HBM bandwidth, over their
device time.  Where ks and lf compile to one program, the trace labels
all its runs with one of the two names; at such a launch ``scan_lf``
takes half of the ``scan_ks``-named runs (``harness.named``).
Weighted by device time, the per-variant shares of ops.scan give its
``pallas_roofline``.
"""
from harness.named import family_roofline_pct


def read(ctx):
    return family_roofline_pct(ctx.trace, ctx.peaks, "scan_lf")
