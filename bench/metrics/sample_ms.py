"""Host sampling per step: self time of the program's ``sample`` spans
over the traced window, per step."""


def read(run):
    s = run.span_self_s(names=("sample",))
    return None if s is None else 1e3 * s / run.steps
