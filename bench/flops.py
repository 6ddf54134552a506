"""Operations and bytes of one GraphSAGE training step, from its shapes.

One ``sage_grads`` call is one trainer's forward and backward pass over
its minibatch: seeds ``(B, F)``, first-hop ``(B, f1, F)`` and second-hop
``(B, f1, f2, F)`` features, a 2-layer mean-aggregator GraphSAGE of
width ``H`` and ``C`` classes. Counted as the model requires them:

* layer 1 runs on the ``B (f1 + 1)`` seed and first-hop rows, two
  ``F x H`` products each, forward and their weight gradients (the
  features are data, so no gradient flows into them);
* layer 2 runs on the ``B`` seeds, two ``H x C`` products, forward,
  weight gradients and the gradients into layer 1's output;
* the mean aggregations: ``B f1 f2 F`` and ``B f1 F`` additions of
  features, ``B f1 H`` of hidden rows and as many in their gradient.

Bytes are the least any implementation moves: every input feature, the
labels and the weights read once, the gradients and the loss written
once, all float32.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class SageShapes:
    batch: int
    fanouts: tuple[int, int]
    feature_dim: int
    hidden: int
    classes: int

    @property
    def params(self) -> int:
        F, H, C = self.feature_dim, self.hidden, self.classes
        return 2 * F * H + H + 2 * H * C + C


def sage_grads_flops(s: SageShapes) -> float:
    B, (f1, f2), F, H, C = s.batch, s.fanouts, s.feature_dim, s.hidden, s.classes
    layer1 = 2 * (2 * 2 * B * (f1 + 1) * F * H)  # forward + weight grads
    layer2 = 3 * (2 * 2 * B * H * C)  # forward + weight + input grads
    means = B * f1 * f2 * F + B * f1 * F + 2 * B * f1 * H
    return float(layer1 + layer2 + means)


def sage_grads_bytes(s: SageShapes) -> float:
    B, (f1, f2), F = s.batch, s.fanouts, s.feature_dim
    inputs = B * (1 + f1 + f1 * f2) * F + B
    return float(4 * (inputs + 2 * s.params + 1))
