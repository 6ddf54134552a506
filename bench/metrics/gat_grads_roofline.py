"""Roofline share of the GAT cells' jitted per-trainer program
(``PROGRAM``, ``gat_grads``): the least time of its executions (the
larger of operations over the bf16 peak and bytes over the HBM
bandwidth, from shapes by ``bench/models/gat.py``'s ``grads_flops`` and
``grads_bytes``) over their summed device time in the profiler trace.
The program gathers its rows from the device feature table, so those
gathers are part of the work it times."""

from bench import devtrace, peaks


def read(run):
    if run.trace is None:
        return None
    seconds, count = devtrace.module_time(run.trace, run.model.PROGRAM)
    if count == 0 or seconds <= 0:
        return None
    pk = peaks.peak(run.device_kind)
    least = max(
        run.model.grads_flops(run.shapes) / pk.flops_bf16,
        run.model.grads_bytes(run.shapes) / pk.hbm_bytes_per_s,
    )
    return 100.0 * least * count / seconds
