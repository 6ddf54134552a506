"""Roofline share of the model's jitted per-trainer program
(``PROGRAM``, ``sage_grads`` here): the least time of its executions
(the larger of operations over the bf16 peak and bytes over the HBM
bandwidth, from shapes by the model's ``grads_flops`` and
``grads_bytes``) over their summed device time in the profiler trace.
The executions are bound by bytes at the cells' shapes.

Since the program gathers its rows from the device feature table, those
gathers are part of the work it times, and the int32 row ids are among
its inputs. The fall from about 67% to about 9.6% that came with that
change is the real cost of the gather, not a stale count."""

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
