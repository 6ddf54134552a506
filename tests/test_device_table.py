"""Training reads its features from a device-resident table: each step
uploads only int32 id blocks, and ``sage_grads`` gathers their rows
inside the program.

* the gathered step equals ``sage_grads`` on blocks gathered on the host
  from ``graph.features``, with and without a feature store;
* the table is uploaded once per ``DistributedTrainer``, whatever the
  number of ``run()`` calls, runtime or device path;
* with a feature store, training reads the store's own rows, so a
  ``poke()`` between runs reaches the next run's loss.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.gnn.sage import Rows, sage_grads
from repro.gnn.train import LANES, DistributedTrainer, index_blocks
from repro.graph import generate, partition_graph

KW = dict(
    variant="fixed", epochs=1, batch_size=16, fanouts=(3, 5),
    hidden_dim=16, buffer_frac=0.25, interval=4,
)


@pytest.fixture(scope="module")
def parts():
    return partition_graph(generate("products", seed=0, scale=0.1), 4)


def _host_blocks(graph, mb):
    f = graph.features
    b, f1 = mb.layer_nbrs[0].shape
    x_n2 = f[mb.layer_nbrs[1]].reshape(b, f1, -1, f.shape[1])
    return f[mb.seeds], f[mb.layer_nbrs[0]], x_n2


@pytest.mark.parametrize("store", [False, True])
def test_gathered_step_matches_host_blocks(parts, store):
    t = DistributedTrainer(parts, feature_store=store, **KW)
    seeds = t._seed_batch(0, 0, 0)
    mb = t.sampler.sample(seeds, np.random.default_rng(3))
    *ids, labels = jax.device_put(index_blocks(mb))
    rows = t.feature_rows(*t.feature_table(), ids)
    assert all(isinstance(r, Rows) for r in rows)
    loss, grads = sage_grads(t.params, *rows, labels)
    ref_loss, ref_grads = sage_grads(t.params, *_host_blocks(t.graph, mb), mb.labels)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    for g, r in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-6, atol=1e-9)


def test_rows_slice_like_their_block(parts):
    t = DistributedTrainer(parts, **KW)
    mb = t.sampler.sample(t._seed_batch(0, 0, 0), np.random.default_rng(3))
    *ids, _ = jax.device_put(index_blocks(mb))
    host = _host_blocks(t.graph, mb)
    for r, x in zip(t.feature_rows(*t.feature_table(), ids), host):
        np.testing.assert_array_equal(np.asarray(r[:5].read()), x[:5])


@pytest.mark.parametrize(
    "path",
    [
        dict(),
        dict(feature_store=True),
        dict(runtime="legacy"),
        dict(device="jnp"),
    ],
    ids=["staged", "store", "legacy", "device"],
)
def test_table_uploaded_once_per_trainer(parts, path):
    t = DistributedTrainer(parts, telemetry=True, **path, **KW)
    uploads = []
    for _ in range(2):
        t.run()
        reg = t.last_telemetry.registry
        uploads.append(
            reg["train.table_uploads"].total if "train.table_uploads" in reg else 0
        )
    assert uploads == [1, 0]
    table, loc = t.feature_table()
    F = t.graph.features.shape[1]
    if t.feature_store is None:
        # rows padded with zeros to whole lanes
        assert loc is None and table.shape == (t.graph.num_nodes, -(-F // LANES) * LANES)
        np.testing.assert_array_equal(np.asarray(table[:, :F]), t.graph.features)
        assert not np.asarray(table[:, F:]).any()
    else:
        assert loc is not None and table.shape[1] == F


def test_poke_between_runs_reaches_the_next_loss(parts):
    twins = [DistributedTrainer(parts, feature_store=True, **KW) for _ in range(2)]
    first = [t.run().losses for t in twins]
    assert first[0] == first[1]
    poked = twins[1]
    seed = int(poked._seed_batch(0, 0, 0)[0])
    poked.feature_store.poke(poked.graph.id_base + seed, delta=100.0)
    second = [t.run().losses for t in twins]
    assert second[0][0] != second[1][0]
