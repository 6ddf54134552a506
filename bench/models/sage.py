"""GraphSAGE: the plain reference, its weights' layout and its work counts.

A configuration with ``"model": "sage"`` is trained as a 2-layer
GraphSAGE with the mean aggregator (Hamilton et al. 2017; DGL's
``SAGEConv(aggregator_type="mean")``: ``h = W_self x + W_nbr
mean(neighbours) + b``, ReLU after layer 1, none after layer 2),
softmax cross-entropy over the seeds, data-parallel gradient mean over
the P trainers and plain SGD. The reference is written in straight
``jax.numpy`` at ``float32`` with every matrix product at
``Precision.HIGHEST``, each trainer's features gathered from the table
on the device. ``dtype=bfloat16`` gives the control: the same step with
weights, features and arithmetic in bfloat16. Nothing here imports the
program.

One ``sage_grads`` call (``PROGRAM``) is one trainer's forward and
backward pass over its minibatch: seeds ``(B, F)``, first-hop
``(B, f1, F)`` and second-hop ``(B, f1, f2, F)`` features, width ``H``
and ``C`` classes. Its operations are counted as the model requires
them:

* layer 1 runs on the ``B (f1 + 1)`` seed and first-hop rows, two
  ``F x H`` products each, forward and their weight gradients (the
  features are data, so no gradient flows into them);
* layer 2 runs on the ``B`` seeds, two ``H x C`` products, forward,
  weight gradients and the gradients into layer 1's output;
* the mean aggregations: ``B f1 f2 F`` and ``B f1 F`` additions of
  features, ``B f1 H`` of hidden rows and as many in their gradient.

Its bytes are the least any implementation that gathers the rows from a
feature table on the device moves: the ``B (1 + f1 + f1 f2)`` int32 row
ids, every gathered feature, the labels and the weights read once, the
gradients and the loss written once, all float32.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import seed_key

HIGHEST = jax.lax.Precision.HIGHEST

#: The jitted per-trainer program, as named in the profiler trace.
PROGRAM = "sage_grads"

#: Leaf order of the weights, as ``layer.kind``.
LEAVES = (
    "layer1.w_self",
    "layer1.w_nbr",
    "layer1.bias",
    "layer2.w_self",
    "layer2.w_nbr",
    "layer2.bias",
)


@dataclass(frozen=True)
class Shapes:
    batch: int
    fanouts: tuple[int, int]
    feature_dim: int
    hidden: int
    classes: int

    @property
    def params(self) -> int:
        F, H, C = self.feature_dim, self.hidden, self.classes
        return 2 * F * H + H + 2 * H * C + C


def shapes(config: dict, trainer_kwargs: dict) -> Shapes:
    """The configuration's graph widths and the job's batch, fanouts and
    hidden width."""
    fanouts = tuple(trainer_kwargs["fanouts"])
    if len(fanouts) != 2:
        raise ValueError(f"GraphSAGE here has 2 layers, the job gives fanouts {fanouts}")
    return Shapes(
        batch=int(trainer_kwargs["batch_size"]),
        fanouts=fanouts,
        feature_dim=int(config["feature_dim"]),
        hidden=int(trainer_kwargs["hidden_dim"]),
        classes=int(config["num_classes"]),
    )


def _leaf_shapes(s: Shapes) -> dict:
    F, H, C = s.feature_dim, s.hidden, s.classes
    return {
        "layer1.w_self": (F, H),
        "layer1.w_nbr": (F, H),
        "layer1.bias": (H,),
        "layer2.w_self": (H, C),
        "layer2.w_nbr": (H, C),
        "layer2.bias": (C,),
    }


def init_weights(seed: int, s: Shapes) -> dict:
    """Glorot-normal weights and zero biases, made on the device in one
    jitted call from ``seed``, in float32 (the type they are trained in)."""
    leaf_shapes = _leaf_shapes(s)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(LEAVES))
        out = {}
        for k, name in zip(keys, LEAVES):
            shape = leaf_shapes[name]
            if len(shape) == 1:
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                scale = (2.0 / (shape[0] + shape[1])) ** 0.5
                out[name] = scale * jax.random.normal(k, shape, jnp.float32)
        return out

    return make(seed_key(seed))


def _dot(a, b):
    return jnp.matmul(a, b, precision=HIGHEST if a.dtype == jnp.float32 else None)


def loss(w: dict, x_seed, x_n1, x_n2, labels):
    """Mean cross-entropy of one trainer's minibatch."""
    def layer(name, x_self, x_nbr_mean):
        return (
            _dot(x_self, w[f"{name}.w_self"])
            + _dot(x_nbr_mean, w[f"{name}.w_nbr"])
            + w[f"{name}.bias"]
        )

    h_n1 = jax.nn.relu(layer("layer1", x_n1, jnp.mean(x_n2, axis=2)))
    h_seed = jax.nn.relu(layer("layer1", x_seed, jnp.mean(x_n1, axis=1)))
    logits = layer("layer2", h_seed, jnp.mean(h_n1, axis=1))
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


@partial(jax.jit, static_argnames="half")
def _trainer_step(w, table, seeds, n1, n2, labels, half=False):
    """One trainer's loss and gradient; its features gathered from the
    table on the device. ``half`` keeps the first half of the seeds."""
    b, f1 = n1.shape
    x_seed, x_n1 = table[seeds], table[n1]
    x_n2 = table[n2.reshape(-1)].reshape(b, f1, -1, table.shape[1])
    if half:
        h = b // 2
        x_seed, x_n1, x_n2, labels = x_seed[:h], x_n1[:h], x_n2[:h], labels[:h]
    return jax.value_and_grad(loss)(w, x_seed, x_n1, x_n2, labels)


def train(
    w0: dict,
    table: jax.Array,
    steps: list[list[tuple]],
    lr: float,
    dtype=jnp.float32,
    *,
    fault: str | None = None,
):
    """Run ``len(steps)`` data-parallel SGD steps from ``w0``.

    ``table`` is the feature table on the device
    (``bench.reference.device_table``). ``steps[t][p]`` is trainer p's
    minibatch at step t, as ``(seeds, hops, labels)`` of node ids, where
    ``hops`` is the sampler's ``layer_nbrs``: ``[(B, f1), (B*f1, f2)]``.
    Returns the losses (mean over trainers), the first step's mean
    gradient and the weights after every step, each as a dict of float64
    numpy leaves.

    ``dtype`` other than float32 gives the control: weights, features
    and arithmetic in that type. ``fault`` plants one of the faults the
    correctness check must catch, for measuring its reading:
    ``"half_batch"`` (each trainer's loss over the first half of its
    seeds) or ``"no_exchange"`` (trainer 0's gradient in place of the
    mean).
    """
    table = table.astype(dtype)
    w = {k: jnp.asarray(v, dtype) for k, v in w0.items()}
    losses, grads1, after = [], None, []
    for batches in steps:
        P = len(batches)
        total, acc = 0.0, None
        for p, (seeds, (n1, n2), labels) in enumerate(batches):
            val, g = _trainer_step(
                w, table, seeds, n1, n2, jnp.asarray(labels, jnp.int32),
                half=fault == "half_batch",
            )
            total += float(val) / P
            if fault == "no_exchange":
                g = jax.tree_util.tree_map(lambda x: x * (P if p == 0 else 0), g)
            acc = g if acc is None else jax.tree_util.tree_map(jnp.add, acc, g)
        mean = jax.tree_util.tree_map(lambda x: x / P, acc)
        if grads1 is None:
            grads1 = {k: np.asarray(v, np.float64) for k, v in mean.items()}
        w = {k: (w[k] - lr * mean[k]).astype(dtype) for k in w}
        losses.append(total)
        after.append({k: np.asarray(v, np.float64) for k, v in w.items()})
    return losses, grads1, after


def grads_flops(s: Shapes) -> float:
    B, (f1, f2), F, H, C = s.batch, s.fanouts, s.feature_dim, s.hidden, s.classes
    layer1 = 2 * (2 * 2 * B * (f1 + 1) * F * H)  # forward + weight grads
    layer2 = 3 * (2 * 2 * B * H * C)  # forward + weight + input grads
    means = B * f1 * f2 * F + B * f1 * F + 2 * B * f1 * H
    return float(layer1 + layer2 + means)


def grads_bytes(s: Shapes) -> float:
    B, (f1, f2), F = s.batch, s.fanouts, s.feature_dim
    rows = B * (1 + f1 + f1 * f2)
    inputs = rows * F + B
    return float(4 * (rows + inputs + 2 * s.params + 1))
