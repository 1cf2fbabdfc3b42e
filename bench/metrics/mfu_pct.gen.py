"""Model FLOP/s utilization of the served model, in percent.

Tokens processed in the traced window (prompt tokens written by prefill
and output tokens decoded, as the harness counted them) times the
model's operations per token from the configuration's widths
(``harness.counts``: projections, SSD state update and readout, and the
tied unembedding for decoded tokens only), over the window and the bf16
peak.  Recomputed or padded work does not count.
"""
from harness.counts import mamba2_flops_per_token


def read(ctx):
    tokens = ctx.readings.get("traced_tokens")
    window = ctx.trace.window_s
    if not tokens or window <= 0:
        return None
    cfg = ctx.cell.config
    flops = (tokens["prompt"] * mamba2_flops_per_token(cfg, logits=False)
             + tokens["output"] * mamba2_flops_per_token(cfg, logits=True))
    return 100.0 * flops / window / ctx.peaks["bf16_flops_per_s"]
