"""Device time of one decode step, in ms: the device time of the
engine's jitted decode-step program (``jit_step``) over its runs in the
traced window."""


def read(ctx):
    seconds, runs = ctx.trace.module_time("jit_step")
    if runs == 0:
        return None
    return seconds / runs * 1e3
