"""Roofline share of the jitted ``sage_grads``: the least time of its
executions (the larger of operations over the bf16 peak and bytes over
the HBM bandwidth, from shapes) over their summed device time in the
profiler trace. The executions are bound by bytes at the cells' shapes
(``bench.flops``)."""

from bench import devtrace, flops, peaks


def read(run):
    if run.trace is None:
        return None
    seconds, count = devtrace.module_time(run.trace, "sage_grads")
    if count == 0 or seconds <= 0:
        return None
    pk = peaks.peak(run.device_kind)
    least = max(
        flops.sage_grads_flops(run.shapes) / pk.flops_bf16,
        flops.sage_grads_bytes(run.shapes) / pk.hbm_bytes_per_s,
    )
    return 100.0 * least * count / seconds
