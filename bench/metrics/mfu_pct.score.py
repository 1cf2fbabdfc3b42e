"""Model FLOP/s utilization of the scoring calls, in percent.

The calls issued in the traced window (each completes in it) times one
call's operations, from the configuration's widths by its reference's
``counts`` (every matmul parameter twice a position, attention's causal
products, the SSD recurrence), over the window and the bf16 peak.
Recomputed or padded work does not count.
"""


def read(ctx):
    calls = ctx.readings.get("traced_calls")
    window = ctx.trace.window_s
    if not calls or window <= 0:
        return None
    cfg = ctx.cell.config
    ref = ctx.cell.module("reference", cfg["reference"])
    flops, _ = ref.counts(cfg, int(ctx.readings["tokens_per_call"]))
    return 100.0 * calls * flops / window / ctx.peaks["bf16_flops_per_s"]
