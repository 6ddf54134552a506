"""GraphSAGE (mean aggregator) in pure JAX.

2-layer model over sampled neighborhood trees, exactly the paper's
training workload (node classification, fanout {10, 25}, batch 2000 at
full scale). The forward consumes the dense padded blocks produced by
:class:`repro.graph.sampler.NeighborSampler`:

    x_seed : (B, F)          seed features
    x_n1   : (B, f1, F)      sampled neighbors of seeds
    x_n2   : (B, f1, f2, F)  sampled neighbors of those neighbors

Each block may also be given as :class:`Rows`: the block's node ids into
a device-resident feature table, gathered inside the program that reads
them, so only the ids cross the host boundary.

Aggregation is a mean over the fanout axis — the same segment-mean that
``kernels/segment_sum`` implements as a Pallas TPU kernel for the
CSR-ordered (variable-degree) full-graph case.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
class Rows:
    """The feature block of an id block, left in a device table until a
    program reads it: ``table[loc[idx]]``, or ``table[idx]`` without
    ``loc``, of shape ``idx.shape + (F,)``; a table wider than ``F``
    (padded to whole lanes) is cut to its first ``F`` columns. Indexing
    selects ids as it would the block (``rows[:h]`` holds the first
    ``h`` nodes' rows). :meth:`fanout_mean` reduces the block and
    :meth:`fanout_major` lays it out fanout first, each without laying
    it out in its own shape first."""

    def __init__(self, table, loc, idx, width: int):
        self.table, self.loc, self.idx, self.width = table, loc, idx, width

    def tree_flatten(self):
        return (self.table, self.loc, self.idx), self.width

    @classmethod
    def tree_unflatten(cls, width, children):
        return cls(*children, width)

    def __getitem__(self, key) -> "Rows":
        return Rows(self.table, self.loc, self.idx[key], self.width)

    def _gather(self, idx) -> jax.Array:
        rows = idx if self.loc is None else self.loc[idx]
        return self.table[rows]

    def read(self) -> jax.Array:
        return self._gather(self.idx)[..., : self.width]

    def fanout_mean(self) -> jax.Array:
        """Mean over the last id axis, gathered fanout-major: the
        ``(f, n, F)`` rows keep the tiles of the gather's own output, so
        nothing is laid out again before the sum."""
        f = self.idx.shape[-1]
        idx = jnp.moveaxis(self.idx, -1, 0).reshape(f, -1)
        mean = jnp.mean(self._gather(idx), axis=0)[:, : self.width]
        return mean.reshape(self.idx.shape[:-1] + (self.width,))

    def fanout_major(self) -> jax.Array:
        """The rows with the id axes reversed, as ``(f, n, F)``: ``f``
        the last id axis, ``n`` the others, the first varying fastest.
        Row ``[j, i]`` is then the ``j``-th child of the ``i``-th row of
        the block one hop up read the same way, so every hop's rows line
        up with the next hop's as the gather lays them out."""
        idx = jnp.transpose(self.idx)
        return self._gather(idx.reshape(idx.shape[0], -1))[..., : self.width]


def _block(x) -> jax.Array:
    return x.read() if isinstance(x, Rows) else x


def _fanout_mean(x) -> jax.Array:
    """Mean over a block's fanout axis, the last before the features."""
    return x.fanout_mean() if isinstance(x, Rows) else jnp.mean(x, axis=-2)


class SageLayer(NamedTuple):
    w_self: jax.Array
    w_nbr: jax.Array
    bias: jax.Array


class SageParams(NamedTuple):
    layer1: SageLayer
    layer2: SageLayer


def init_sage(
    key: jax.Array, feature_dim: int, hidden_dim: int, num_classes: int
) -> SageParams:
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def glorot(k, a, b):
        return jax.random.normal(k, (a, b), dtype=jnp.float32) * (
            2.0 / (a + b)
        ) ** 0.5

    return SageParams(
        layer1=SageLayer(
            w_self=glorot(k1, feature_dim, hidden_dim),
            w_nbr=glorot(k2, feature_dim, hidden_dim),
            bias=jnp.zeros((hidden_dim,), jnp.float32),
        ),
        layer2=SageLayer(
            w_self=glorot(k3, hidden_dim, num_classes),
            w_nbr=glorot(k4, hidden_dim, num_classes),
            bias=jnp.zeros((num_classes,), jnp.float32),
        ),
    )


def _sage_combine(layer: SageLayer, x_self: jax.Array, x_nbr_mean: jax.Array):
    return x_self @ layer.w_self + x_nbr_mean @ layer.w_nbr + layer.bias


def sage_forward(
    params: SageParams,
    x_seed: jax.Array,
    x_n1: jax.Array,
    x_n2: jax.Array,
) -> jax.Array:
    """Returns logits (B, num_classes); any block may be :class:`Rows`."""
    # Layer 1 applied to every node that layer 2 will read.
    h_n1 = jax.nn.relu(
        _sage_combine(params.layer1, _block(x_n1), _fanout_mean(x_n2))
    )  # (B, f1, H)
    h_seed = jax.nn.relu(
        _sage_combine(params.layer1, _block(x_seed), _fanout_mean(x_n1))
    )  # (B, H)
    # Layer 2 on seeds.
    logits = _sage_combine(params.layer2, h_seed, jnp.mean(h_n1, axis=1))
    return logits


def sage_loss(
    params: SageParams,
    x_seed: jax.Array,
    x_n1: jax.Array,
    x_n2: jax.Array,
    labels: jax.Array,
) -> jax.Array:
    logits = sage_forward(params, x_seed, x_n1, x_n2)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


@jax.jit
def sage_train_step(
    params: SageParams,
    x_seed: jax.Array,
    x_n1: jax.Array,
    x_n2: jax.Array,
    labels: jax.Array,
    lr: float = 1e-2,
):
    """Single-trainer SGD step; the distributed driver averages grads
    across trainers before applying (data-parallel semantics)."""
    loss, grads = jax.value_and_grad(sage_loss)(params, x_seed, x_n1, x_n2, labels)
    new_params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
    return new_params, loss


@jax.jit
def sage_grads(params, x_seed, x_n1, x_n2, labels):
    return jax.value_and_grad(sage_loss)(params, x_seed, x_n1, x_n2, labels)


@jax.jit
def sage_accuracy(params, x_seed, x_n1, x_n2, labels):
    logits = sage_forward(params, x_seed, x_n1, x_n2)
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
