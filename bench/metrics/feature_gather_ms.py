"""Host feature gather of the training step per step: self time of the
program's ``train.gather`` spans (every trainer's ``x_seed``, ``x_n1``
and ``x_n2`` gathered from the feature table) over the traced window,
per step."""


def read(run):
    s = run.span_self_s(names=("train.gather",))
    return None if s is None else 1e3 * s / run.steps
