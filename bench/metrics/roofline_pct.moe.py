"""Roofline share of the MoE expert share's grouped matmuls, in percent.

The program names the two grouped-matmul launches of each MoE layer
``moe_gmm_up`` and ``moe_gmm_down``.  Over the launches so named in the
traced window, the least time the work needs, max(flops ÷ the bf16 peak,
bytes ÷ the HBM bandwidth), over their device time.  Flops and bytes are
the calls issued in the window times one call's routed work, from the
configuration by its reference's ``moe_counts``: each expected routed row
(tokens x picks x held ÷ experts) through both matmuls, the held experts'
weights read once and the rows in and out; padding never counts.  The
launches' operand shapes are not used: they hold the rows the held
experts could receive at most, not the rows they do.  A program that
names no such launch reads None.
"""
from trace_reduce import op_name

FAMILY = "moe_gmm"


def _named(hlo: str) -> bool:
    base = op_name(hlo).split(".", 1)[0]
    return base == FAMILY or base.startswith(FAMILY + "_")


def read(ctx):
    calls = ctx.readings.get("traced_calls")
    busy = sum(op.dur for op, _ in ctx.trace.pallas_calls()
               if _named(op.hlo))
    if not calls or busy <= 0:
        return None
    cfg = ctx.cell.config
    ref = ctx.cell.module("reference", cfg["reference"])
    flops, nbytes = ref.moe_counts(cfg, int(ctx.readings["tokens_per_call"]))
    least = calls * max(flops / ctx.peaks["bf16_flops_per_s"],
                        nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
