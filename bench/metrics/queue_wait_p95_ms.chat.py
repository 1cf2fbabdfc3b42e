"""Scheduler queue time, in ms: the 95th percentile (nearest rank), over
the requests due in the measured window, of admission (``Request.t_admit``)
minus when the request was due.  It counts the time a request waited for
the client loop to submit it as well as the time it waited in the
engine's queue."""
from harness.stats import percentile


def read(ctx):
    waits = ctx.readings.get("queue_wait_s")
    if not waits:
        return None
    return percentile(waits, 95) * 1e3
