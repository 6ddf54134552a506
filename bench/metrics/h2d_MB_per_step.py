"""Host-to-device bytes per step: the program's ``device.h2d_bytes``
counter over the traced window (its session is live for the window
only), in MB per step. On the staged path that is every trainer's
training upload; None untraced or where the program counts none."""


def read(run):
    if run.session is None or "device.h2d_bytes" not in run.session.registry:
        return None
    return run.session.registry["device.h2d_bytes"].total / 1e6 / run.steps
