"""Plain references the benchmark compares the program against.

Nothing here imports the program. Two references:

* GraphSAGE training: a 2-layer GraphSAGE with the mean aggregator
  (Hamilton et al. 2017; DGL's ``SAGEConv(aggregator_type="mean")``:
  ``h = W_self x + W_nbr mean(neighbours) + b``, ReLU after layer 1, none
  after layer 2), softmax cross-entropy over the seeds, data-parallel
  gradient mean over the P trainers and plain SGD. Written in straight
  ``jax.numpy`` at ``float32`` with every matrix product at
  ``Precision.HIGHEST``, each trainer's features gathered from the
  table on the device. ``dtype=bfloat16`` gives the control: the same
  step with weights, features and arithmetic in bfloat16.
* Prefetch accounting: one fixed-capacity buffer per trainer under the
  paper's frequency policy (section 2.1: +1 on access, x0.95 when idle,
  stale below 0.95, newcomers at 1.0), probed with each minibatch's
  unique remote nodes and refilled with the previous minibatch's misses
  when the controller says so. It yields the per-step remote, miss and
  admission counts that ``remote_MB_per_step`` and ``buffer_hit_pct``
  are read from.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

#: Leaf order of the weights, as ``layer.kind``.
LEAVES = (
    "layer1.w_self",
    "layer1.w_nbr",
    "layer1.bias",
    "layer2.w_self",
    "layer2.w_nbr",
    "layer2.bias",
)


def leaf_shapes(feature_dim: int, hidden: int, classes: int) -> dict:
    return {
        "layer1.w_self": (feature_dim, hidden),
        "layer1.w_nbr": (feature_dim, hidden),
        "layer1.bias": (hidden,),
        "layer2.w_self": (hidden, classes),
        "layer2.w_nbr": (hidden, classes),
        "layer2.bias": (classes,),
    }


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that depends on all 64 bits of ``seed``."""
    key = jax.random.PRNGKey(0x5A6E)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed & 0xFFFFFFFF)


def init_weights(seed: int, feature_dim: int, hidden: int, classes: int) -> dict:
    """Glorot-normal weights and zero biases, made on the device in one
    jitted call from ``seed``, in float32 (the type they are trained in)."""
    shapes = leaf_shapes(feature_dim, hidden, classes)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(LEAVES))
        out = {}
        for k, name in zip(keys, LEAVES):
            shape = shapes[name]
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


def device_table(features: np.ndarray, dtype=jnp.float32) -> jax.Array:
    """The feature table on the device, in ``dtype``."""
    return jnp.asarray(features).astype(dtype)


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

    ``table`` is the feature table on the device (``device_table``).
    ``steps[t][p]`` is trainer p's minibatch at step t, as ``(seeds,
    nbrs1 (B, f1), nbrs2 (B*f1, f2), labels)`` of node ids. Returns the
    losses (mean over trainers), the first step's mean gradient and the
    weights after every step, each as a dict of float64 numpy leaves.

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
        for p, (seeds, n1, n2, labels) in enumerate(batches):
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


# --------------------------------------------------------------------- #
# Prefetch accounting
# --------------------------------------------------------------------- #
INCREMENT = np.float32(1.0)
DECAY = np.float32(0.95)
STALE_BELOW = np.float32(0.95)
INITIAL = np.float32(1.0)


class Buffer:
    """One trainer's buffer: ids, float32 scores, valid and accessed flags,
    and the slot of every node of the graph (-1 where it is not held)."""

    def __init__(self, capacity: int, num_nodes: int):
        self.ids = np.full(capacity, -1, np.int64)
        self.scores = np.zeros(capacity, np.float32)
        self.valid = np.zeros(capacity, bool)
        self.accessed = np.zeros(capacity, bool)
        self.slot = np.full(num_nodes, -1, np.int64)

    def slots_of(self, query: np.ndarray) -> np.ndarray:
        """Slot of each queried id, -1 where it is not held."""
        return self.slot[query]

    def probe(self, remote: np.ndarray) -> np.ndarray:
        slots = self.slots_of(remote)
        hit = slots >= 0
        self.accessed[slots[hit]] = True
        return hit

    def end_round(self) -> None:
        new = np.where(self.accessed, self.scores + INCREMENT, self.scores * DECAY)
        self.scores = np.where(self.valid, new, self.scores).astype(np.float32)
        self.accessed[:] = False

    def replace(self, candidates: np.ndarray) -> int:
        cand = candidates[self.slots_of(candidates) < 0]
        stale = self.valid & (self.scores < STALE_BELOW)
        slots = np.concatenate([np.nonzero(~self.valid)[0], np.nonzero(stale)[0]])
        n = min(len(slots), len(cand))
        s = slots[:n]
        self.slot[self.ids[s][self.valid[s]]] = -1
        self.ids[s] = cand[:n]
        self.slot[cand[:n]] = s
        self.scores[s] = INITIAL
        self.valid[s] = True
        self.accessed[s] = False
        return n


def remote_set(batch, part_of: np.ndarray, p: int) -> np.ndarray:
    """Sorted unique nodes of one minibatch homed on another partition."""
    seeds, n1, n2, _ = batch
    touched = np.unique(np.concatenate([seeds, n1.reshape(-1), n2.reshape(-1)]))
    return touched[part_of[touched] != p]


class Accounting:
    """Replays the prefetch accounting of every step of every ``run()``.

    ``uses_buffer`` is False for the no-prefetch baseline (every remote
    node is fetched and nothing is admitted). Within one ``run()`` call a
    replacement round admits the previous step's misses; the first
    step of a call has none to admit, and buffers persist across calls.
    """

    def __init__(self, capacities: list[int], part_of: np.ndarray, uses_buffer: bool):
        self.part_of = part_of
        self.uses_buffer = uses_buffer
        self.buffers = [Buffer(c, len(part_of)) for c in capacities]

    def run(self, steps: list[list[tuple]], decisions: np.ndarray) -> dict:
        """``decisions[t, p]``: the controller's replace decision. Returns
        per-step, per-trainer ``remote``, ``missed`` and ``replaced``."""
        T, P = len(steps), len(self.buffers)
        out = {k: np.zeros((T, P), np.int64) for k in ("remote", "missed", "replaced")}
        prev = [np.zeros(0, np.int64) for _ in range(P)]
        for t, batches in enumerate(steps):
            for p, buf in enumerate(self.buffers):
                remote = remote_set(batches[p], self.part_of, p)
                active = self.uses_buffer and len(buf.ids) > 0
                hit = buf.probe(remote) if active else np.zeros(len(remote), bool)
                missed = remote[~hit]
                replaced = 0
                if self.uses_buffer:
                    buf.end_round()
                    if decisions[t, p]:
                        replaced = buf.replace(prev[p])
                prev[p] = missed
                out["remote"][t, p] = len(remote)
                out["missed"][t, p] = len(missed)
                out["replaced"][t, p] = replaced
        return out
