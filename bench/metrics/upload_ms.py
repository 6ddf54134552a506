"""Upload of the training step's inputs per step: self time of the
program's ``train.upload`` spans (one ``jax.device_put`` of a trainer's
features and labels, waited for while traced) over the traced window,
per step."""


def read(run):
    s = run.span_self_s(names=("train.upload",))
    return None if s is None else 1e3 * s / run.steps
