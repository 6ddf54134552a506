"""Share of the traced window in which no operation ran on the device:
one minus the union of the device-op intervals over the window."""

from bench import devtrace


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    return 100.0 * (1.0 - devtrace.busy_s(run.trace) / devtrace.window_s(run.trace))
