"""The whole training step's share of the chip's bf16 peak in the GAT
cells: the model's forward and backward operations of every trainer's
minibatch (counted from shapes by ``grads_flops``, ``bench/models/gat.py``,
in the cheaper exact order of each layer) per second of the traced
window."""

from bench import peaks


def read(run):
    if run.trace is None:
        return None
    ops = run.model.grads_flops(run.shapes) * run.trainers * run.steps
    return 100.0 * ops / run.window_s / peaks.peak(run.device_kind).flops_bf16
