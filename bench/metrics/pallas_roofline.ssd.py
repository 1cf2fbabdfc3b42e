"""``pallas_roofline`` of the SSD kernels alone, in percent.

The program names the Mamba-2 SSD op's launches ``ssd_chunk`` (the
intra-chunk phase), ``ssd_carry`` (the inter-chunk state: the fused
state-and-apply launch, or unfused the embedded linear-recurrence scan)
and ``ssd_apply`` (the unfused apply).  The share is their operand plus
result bytes over the HBM bandwidth, over their device time
(``harness.named``).  A program that does not name them reads None.
"""
from harness.named import family_roofline_pct


def read(ctx):
    return family_roofline_pct(ctx.trace, ctx.peaks, "ssd")
