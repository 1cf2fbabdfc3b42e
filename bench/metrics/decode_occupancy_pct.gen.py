"""Decode batch occupancy in the traced window, in percent: output
tokens the host saw in the window over the engine's decode dispatches
there (``serve.step`` spans) times ``max_batch``."""
from harness.named import spans


def read(ctx):
    tokens = ctx.readings.get("traced_tokens")
    steps = len(spans(ctx.trace, "serve.step"))
    if not tokens or steps == 0:
        return None
    lanes = int(ctx.cell.settings["engine"]["max_batch"])
    return 100.0 * tokens["output"] / (steps * lanes)
