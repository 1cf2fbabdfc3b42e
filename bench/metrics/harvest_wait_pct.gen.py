"""Share of the traced window the engine's host loop spends in
``serve.harvest``, in percent: the blocking fetch of the emitted tokens
(the host waits there for the device) and the loop over the rows."""
from harness.named import span_seconds, spans


def read(ctx):
    window = ctx.trace.window_s
    if window <= 0 or not spans(ctx.trace, "serve.step"):
        return None
    return 100.0 * span_seconds(ctx.trace, "serve.harvest") / window
