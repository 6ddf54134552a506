"""What the benchmark's references share, and the prefetch reference.

Nothing here imports the program. Each model's training reference is a
module of its own under ``bench/models`` (``harness.load_model``); they
take their weights' key from :func:`seed_key` and their features from
:func:`device_table`.

Prefetch accounting: one fixed-capacity buffer per trainer under the
paper's frequency policy (section 2.1: +1 on access, x0.95 when idle,
stale below 0.95, newcomers at 1.0), probed with each minibatch's
unique remote nodes and refilled with the previous minibatch's misses
when the controller says so. It yields the per-step remote, miss and
admission counts that ``remote_MB_per_step`` and ``buffer_hit_pct``
are read from.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that depends on all 64 bits of ``seed``."""
    key = jax.random.PRNGKey(0x5A6E)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed & 0xFFFFFFFF)


def device_table(features: np.ndarray, dtype=jnp.float32) -> jax.Array:
    """The feature table on the device, in ``dtype``."""
    return jnp.asarray(features).astype(dtype)


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
    seeds, hops, _ = batch
    touched = np.unique(np.concatenate([seeds, *(h.reshape(-1) for h in hops)]))
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
