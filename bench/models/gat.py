"""GAT: the plain reference, its weights' layout and its work counts.

A configuration with ``"model": "gat"`` is trained as OGB's "GAT w/NS"
for ogbn-products (PyG ``examples/ogbn_products_gat.py``; Veličković et
al., ICLR 2018): one ``GATConv`` per hop, ``heads`` heads of ``hidden``
channels concatenated, the last layer ``classes`` channels with its heads
averaged. For a target ``i`` whose sources are its sampled children and
itself (the self loop), per head ``h``::

    e_ij  = LeakyReLU_0.2(a_src · W_h x_j + a_dst · W_h x_i)
    α_ij  = softmax_j(e_ij)
    out_i = concat_h | mean_h (Σ_j α_ij W_h x_j) + b + Lin(x_i)

``W`` is shared by sources and targets, ``Lin`` is a linear skip with
bias, ELU follows every layer but the last; softmax cross-entropy over
the seeds, data-parallel gradient mean over the P trainers and plain
SGD. The minibatch is the sampler's tree: every hop-``k`` position is a
target of its own ``f_{k+1}`` children. The reference is written in
straight ``jax.numpy`` at ``float32`` with every matrix product at
``Precision.HIGHEST``, each hop's rows gathered from the table on the
device in the block's own shape ``(B, f1, ..., fk, F)``, the self row
put before the children and the softmax taken over both. ``dtype=
bfloat16`` gives the control. Nothing here imports the program.

One ``gat_grads`` call (``PROGRAM``) is one trainer's forward and
backward pass. Hop ``k`` holds ``n_k = B f1 ... fk`` rows; layer ``l``
reads hops ``0..L-l+1`` (``n_in`` rows of width ``D``) and writes hops
``0..L-l`` (``n_t`` targets, ``E`` edges counting self loops, output
width ``O``, ``heads x C`` projected channels). Two exact orders compute
a layer:

* as published: project every input row, ``2 n_in D heads C``; score
  sources and targets, ``2 (n_in + n_t) heads C``; weight and sum the
  projected sources, ``2 E heads C``;
* reordered: fold ``W_h^T a`` into one vector per head and side
  (``4 D heads C``), score every row with it, ``2 (n_in + n_t) D heads``;
  weight and sum the raw source rows per head, ``2 E D heads``; project
  each target's sum once per head, ``2 n_t D heads C``.

The skip, ``2 n_t D O``, is in both. At the cell's shapes the published
order is about 99 GFLOP forward and the reordered one about 19, because
the reordered one projects 56,832 targets where the published one
projects all 568,832 rows. ``grads_flops`` counts, per layer, the
cheaper order: a later program that reorders must not read over the
chip's peak. The backward is counted as the GraphSAGE module counts it:
each product once forward and once more for each operand that carries a
gradient (the features are data; in layer 1 the raw-row sums and scores
take their gradient through the weights' side only). Softmax,
LeakyReLU, ELU, the head mean and bias additions are not counted.

Its bytes are the least any implementation that gathers the rows from a
feature table on the device moves: the ``Σ n_k`` int32 row ids, every
gathered feature, the labels and the weights read once, the gradients
and the loss written once, all float32.
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
PROGRAM = "gat_grads"

#: One layer's leaves, in the order of the program's ``GatLayer``.
KINDS = ("w", "att_src", "att_dst", "bias", "skip_w", "skip_b")


@dataclass(frozen=True)
class Shapes:
    batch: int
    fanouts: tuple[int, ...]
    feature_dim: int
    hidden: int
    heads: int
    classes: int

    def layer_widths(self) -> list[tuple[int, int, int]]:
        """``(D_in, C, O)`` of each layer: input width, channels per
        head and output width."""
        L, H, k = len(self.fanouts), self.hidden, self.heads
        out = []
        d = self.feature_dim
        for i in range(L):
            c = self.classes if i == L - 1 else H
            o = c if i == L - 1 else k * c
            out.append((d, c, o))
            d = o
        return out

    @property
    def params(self) -> int:
        k = self.heads
        return sum(d * k * c + 2 * k * c + o + d * o + o for d, c, o in self.layer_widths())


#: Layers, one per hop of the sampler.
LAYERS = 3

#: Leaf order of the weights, as ``layer.kind``.
LEAVES = tuple(f"layer{i + 1}.{kind}" for i in range(LAYERS) for kind in KINDS)


def shapes(config: dict, trainer_kwargs: dict) -> Shapes:
    """The configuration's graph widths and the job's batch, fanouts,
    heads and hidden width."""
    fanouts = tuple(trainer_kwargs["fanouts"])
    if len(fanouts) != LAYERS:
        raise ValueError(f"GAT here has {LAYERS} layers, the job gives fanouts {fanouts}")
    return Shapes(
        batch=int(trainer_kwargs["batch_size"]),
        fanouts=fanouts,
        feature_dim=int(config["feature_dim"]),
        hidden=int(trainer_kwargs["hidden_dim"]),
        heads=int(trainer_kwargs["heads"]),
        classes=int(config["num_classes"]),
    )


def _leaf_shapes(s: Shapes) -> dict:
    k = s.heads
    out = {}
    for i, (d, c, o) in enumerate(s.layer_widths()):
        shape = {
            "w": (d, k * c), "att_src": (k, c), "att_dst": (k, c),
            "bias": (o,), "skip_w": (d, o), "skip_b": (o,),
        }
        out.update({f"layer{i + 1}.{kind}": shape[kind] for kind in KINDS})
    return out


def init_weights(seed: int, s: Shapes) -> dict:
    """Glorot-normal matrices and attention vectors and zero biases,
    made on the device in one jitted call from ``seed``, in float32."""
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


def _conv(w: dict, name: str, x_tgt, x_kids, last: bool):
    """One layer at targets ``x_tgt (..., D)`` with children
    ``x_kids (..., f, D)``."""
    heads, c = w[f"{name}.att_src"].shape
    src = jnp.concatenate([x_tgt[..., None, :], x_kids], axis=-2)     # (..., 1+f, D)
    p = _dot(src, w[f"{name}.w"]).reshape(src.shape[:-1] + (heads, c))
    e = jax.nn.leaky_relu(
        jnp.sum(p * w[f"{name}.att_src"], axis=-1)
        + jnp.sum(p[..., :1, :, :] * w[f"{name}.att_dst"], axis=-1),
        0.2,
    )                                                                 # (..., 1+f, heads)
    a = jax.nn.softmax(e, axis=-2)
    out = jnp.sum(a[..., None] * p, axis=-3)                          # (..., heads, C)
    out = jnp.mean(out, axis=-2) if last else out.reshape(out.shape[:-2] + (heads * c,))
    return out + w[f"{name}.bias"] + _dot(x_tgt, w[f"{name}.skip_w"]) + w[f"{name}.skip_b"]


def loss(w: dict, xs: list, labels):
    """Mean cross-entropy of one trainer's minibatch; ``xs[k]`` is hop
    ``k``'s features in the block's shape ``(B, f1, ..., fk, F)``."""
    L = len(xs) - 1
    for i in range(L):
        last = i == L - 1
        xs = [_conv(w, f"layer{i + 1}", xs[k], xs[k + 1], last) for k in range(len(xs) - 1)]
        if not last:
            xs = [jax.nn.elu(x) for x in xs]
    logp = jax.nn.log_softmax(xs[0].astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


@partial(jax.jit, static_argnames="half")
def _trainer_step(w, table, seeds, hops, labels, half=False):
    """One trainer's loss and gradient; its features gathered from the
    table on the device. ``half`` keeps the first half of the seeds."""
    xs = [table[seeds]]
    shape = seeds.shape
    for h in hops:
        shape += h.shape[-1:]
        xs.append(table[h.reshape(-1)].reshape(shape + table.shape[1:]))
    if half:
        b = len(seeds) // 2
        xs, labels = [x[:b] for x in xs], labels[:b]
    return jax.value_and_grad(loss)(w, xs, labels)


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
    ``hops`` is the sampler's ``layer_nbrs``: ``[(B, f1), (B*f1, f2),
    (B*f1*f2, f3)]``. Returns the losses (mean over trainers), the first
    step's mean gradient and the weights after every step, each as a
    dict of float64 numpy leaves.

    ``dtype`` other than float32 gives the control: weights, features
    and arithmetic in that type. ``fault`` plants ``"half_batch"`` (each
    trainer's loss over the first half of its seeds) or
    ``"no_exchange"`` (trainer 0's gradient in place of the mean).
    """
    table = table.astype(dtype)
    w = {k: jnp.asarray(v, dtype) for k, v in w0.items()}
    losses, grads1, after = [], None, []
    for batches in steps:
        P = len(batches)
        total, acc = 0.0, None
        for p, (seeds, hops, labels) in enumerate(batches):
            val, g = _trainer_step(
                w, table, jnp.asarray(seeds), tuple(jnp.asarray(h) for h in hops),
                jnp.asarray(labels, jnp.int32), half=fault == "half_batch",
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


def _hop_rows(s: Shapes) -> list[int]:
    n = [s.batch]
    for f in s.fanouts:
        n.append(n[-1] * f)
    return n


def layer_flops(s: Shapes) -> list[tuple[float, float]]:
    """Forward and backward operations of each layer, ``(published,
    reordered)``, each product counted once forward and once for each
    operand that carries a gradient."""
    n, L, k = _hop_rows(s), len(s.fanouts), s.heads
    out = []
    for i, (d, c, o) in enumerate(s.layer_widths()):
        n_in, n_t = sum(n[: L - i + 1]), sum(n[: L - i])
        edges = n_in - n[0] + n_t
        data = 2 if i == 0 else 3  # operands with a gradient, plus the forward
        published = (
            data * 2 * n_in * d * k * c            # projection of every row
            + 3 * 2 * (n_in + n_t) * k * c         # scores
            + 3 * 2 * edges * k * c                # weighted sums
            + data * 2 * n_t * d * o               # skip
        )
        reordered = (
            3 * 4 * d * k * c                      # W_h^T a, both sides
            + data * 2 * (n_in + n_t) * d * k      # scores of the rows
            + data * 2 * edges * d * k             # weighted sums of the rows
            + 3 * 2 * n_t * d * k * c              # projection of the sums
            + data * 2 * n_t * d * o               # skip
        )
        out.append((float(published), float(reordered)))
    return out


def grads_flops(s: Shapes) -> float:
    return float(sum(min(layer) for layer in layer_flops(s)))


def grads_bytes(s: Shapes) -> float:
    rows = sum(_hop_rows(s))
    inputs = rows * s.feature_dim + s.batch
    return float(4 * (rows + inputs + 2 * s.params + 1))
