"""Gradient computation of the training step per step: self time of the
program's ``train.grads`` spans (the ``sage_grads`` dispatch, its device
time and the loss read back, every trainer) over the traced window, per
step."""


def read(run):
    s = run.span_self_s(names=("train.grads",))
    return None if s is None else 1e3 * s / run.steps
