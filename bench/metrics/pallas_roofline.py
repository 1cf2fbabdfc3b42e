"""Share of the roofline reached inside the Pallas kernels, in percent.

Every Pallas kernel run in the traced window is found by its HLO
(``custom_call_target="tpu_custom_call"``), not by name.  Its least time
is its operand plus result bytes over the HBM bandwidth (every kernel of
the paper's ops is bound by bytes at these sizes); the share is the sum
of least times over the sum of the kernels' device times.
"""


def read(ctx):
    calls = ctx.trace.pallas_calls()
    busy = sum(op.dur for op, _ in calls)
    if not calls or busy <= 0:
        return None
    least = sum(nbytes for _, nbytes in calls) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / busy
