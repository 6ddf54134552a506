"""Prefetch engine per step: self time of the ``fetch.*``, ``fused.*``,
``device.launch``, ``device.readback`` and ``store.gather`` spans over
the traced window, per step."""


def read(run):
    s = run.span_self_s(
        names=("device.launch", "device.readback", "store.gather"),
        prefixes=("fetch.", "fused."),
    )
    return None if s is None else 1e3 * s / run.steps
