"""Training step per step: inclusive time of the ``train`` spans over
the traced window, per step. It holds every trainer's id blocks, their
upload, ``sage_grads`` and the loss read back, and the weight update:
the sum of ``feature_gather_ms``, ``upload_ms``, ``grads_ms``,
``update_ms`` and the loop's own residue."""


def read(run):
    s = run.span_total_s(names=("train",))
    return None if s is None else 1e3 * s / run.steps
