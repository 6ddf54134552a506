"""Weight update of the training step per step: self time of the
program's ``train.update`` spans (the gradients' sum over trainers,
their mean and the SGD step) over the traced window, per step."""


def read(run):
    s = run.span_self_s(names=("train.update",))
    return None if s is None else 1e3 * s / run.steps
