"""The whole training step's share of the chip's bf16 peak: GraphSAGE
forward and backward operations of every trainer's minibatch (counted
from shapes by ``bench.flops``) per second of the traced window."""

from bench import flops, peaks


def read(run):
    if run.trace is None:
        return None
    ops = flops.sage_grads_flops(run.shapes) * run.trainers * run.steps
    return 100.0 * ops / run.window_s / peaks.peak(run.device_kind).flops_bf16
