"""Training step per step: self time of the ``train`` spans over the
traced window, per step. It holds the host gather of every trainer's
features, their upload, ``sage_grads`` and the loss read back."""


def read(run):
    s = run.span_self_s(names=("train",))
    return None if s is None else 1e3 * s / run.steps
