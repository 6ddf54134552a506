"""Decision plane per step: self time of the ``decision`` and
``agent.infer`` spans over the traced window, per step."""


def read(run):
    s = run.span_self_s(names=("decision", "agent.infer"))
    return None if s is None else 1e3 * s / run.steps
