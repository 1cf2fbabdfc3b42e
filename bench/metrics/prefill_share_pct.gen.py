"""Share of the device's busy time spent in the engine's prefill
programs (``jit_prefill``), in percent."""


def read(ctx):
    seconds, runs = ctx.trace.module_time("jit_prefill")
    busy = ctx.trace.busy_s
    if busy <= 0:
        return None
    return 100.0 * seconds / busy
