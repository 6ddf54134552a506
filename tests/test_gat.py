"""GAT (``gnn/gat.py``) on the CPU at a tiny size, with seeded random
weights: its loss and gradients against the benchmark's plain reference
(``bench/models/gat.py``, an independent write-up of the published
equations), one layer against a published-order oracle, two closed
forms of one layer, the published parameter count, the L-hop id blocks
and the trainer's refusals."""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.gnn import gat
from repro.gnn.sage import Rows
from repro.gnn.train import LANES, DistributedTrainer, index_blocks
from repro.graph import generate, partition_graph
from repro.graph.sampler import MiniBatch

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.models import gat as ref  # noqa: E402

N, F, B, FANOUTS, HEADS, HIDDEN, CLASSES = 300, 20, 4, (3, 2, 2), 2, 8, 5


def _problem(seed):
    """Features, a minibatch of random ids, the reference's weights and
    the program's from the same leaves."""
    rng = np.random.default_rng(seed)
    table = rng.normal(size=(N, F)).astype(np.float32)
    seeds = rng.integers(0, N, B)
    hops, n = [], B
    for f in FANOUTS:
        hops.append(rng.integers(0, N, (n, f)))
        n *= f
    labels = rng.integers(0, CLASSES, B)
    s = ref.shapes(
        {"feature_dim": F, "num_classes": CLASSES},
        {"batch_size": B, "fanouts": FANOUTS, "hidden_dim": HIDDEN, "heads": HEADS},
    )
    w = ref.init_weights(seed, s)
    treedef = jax.tree_util.tree_structure(
        jax.eval_shape(lambda: gat.init_gat(jax.random.PRNGKey(0), F, HIDDEN, CLASSES, HEADS))
    )
    params = jax.tree_util.tree_unflatten(treedef, [w[k] for k in ref.LEAVES])
    mb = MiniBatch(seeds, hops, None, labels)
    return table, mb, w, params


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
@pytest.mark.parametrize("form", ["dense", "rows"])
def test_grads_match_the_reference(seed, form):
    table, mb, w, params = _problem(seed)
    *ids, labels = index_blocks(mb)
    if form == "dense":
        blocks = [jnp.asarray(table[i]) for i in ids]
    else:
        padded = jnp.asarray(np.pad(table, ((0, 0), (0, LANES - F))))
        blocks = [Rows(padded, None, jnp.asarray(i), F) for i in ids]
    loss, grads = gat.gat_grads(params, blocks, jnp.asarray(labels))
    ref_loss, ref_grads = ref._trainer_step(
        w, jnp.asarray(table), jnp.asarray(mb.seeds),
        tuple(jnp.asarray(h) for h in mb.layer_nbrs), jnp.asarray(labels),
    )
    # Both sides are float32 with every product at full precision on
    # the CPU; they differ in the order of their sums only (the program
    # sums the self term apart and projects each target's weighted sum
    # of raw rows, the reference projects every row first), a few
    # float32 roundings (6e-8 each) deep. Read here: 1.2e-7 on the
    # loss, 1.24e-6 of a leaf's largest entry on its gradient; the
    # tolerances are about eight times those.
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    for name, g in zip(ref.LEAVES, jax.tree_util.tree_leaves(grads)):
        r = np.asarray(ref_grads[name])
        np.testing.assert_allclose(
            np.asarray(g), r, rtol=1e-5, atol=1e-5 * np.abs(r).max(), err_msg=name
        )


def _published_layer(layer, h, last):
    """The oracle: one layer in the published order, in float64: every
    row projected to ``heads x C``, then scored and summed."""
    w, a_src, a_dst, bias, skip_w, skip_b = (np.asarray(x, np.float64) for x in layer)
    heads, c = a_src.shape
    proj = [np.asarray(x, np.float64) @ w for x in h]
    proj = [p.reshape(len(p), heads, c) for p in proj]
    out = []
    for k in range(len(h) - 1):
        n = len(h[k])
        srcs = np.concatenate([proj[k][None], proj[k + 1].reshape(-1, n, heads, c)])
        e = np.sum(srcs * a_src, -1) + np.sum(proj[k] * a_dst, -1)    # (1+f, n, heads)
        e = np.where(e > 0, e, 0.2 * e)
        a = np.exp(e - e.max(0))
        a /= a.sum(0)
        agg = np.sum(a[..., None] * srcs, axis=0)                      # (n, heads, C)
        agg = agg.mean(1) if last else agg.reshape(n, heads * c)
        out.append(agg + bias + np.asarray(h[k], np.float64) @ skip_w + skip_b)
    return out


@pytest.mark.parametrize("d", [6, 20])
@pytest.mark.parametrize("fanout", [1, 2, 3])
@pytest.mark.parametrize("form", ["dense", "rows"])
@pytest.mark.parametrize("last", [False, True])
def test_reordered_layer_matches_the_published_order(last, form, fanout, d):
    # Heads 3 x 4 channels: d = 6 is below heads x C = 12, d = 20 above.
    heads, c, b = 3, 4, 5
    rng = np.random.default_rng(fanout * 100 + d)
    table = rng.normal(size=(40, d)).astype(np.float32)
    ids = [rng.integers(0, len(table), (b,) + (fanout,) * k) for k in range(3)]
    if form == "dense":
        blocks = [jnp.asarray(table[i]) for i in ids]
    else:
        padded = jnp.asarray(np.pad(table, ((0, 0), (0, LANES - d))))
        blocks = [Rows(padded, None, jnp.asarray(i, jnp.int32), d) for i in ids]
    h = [gat._hop_rows(x) for x in blocks]
    p = gat.init_gat(jax.random.PRNGKey(fanout), d, c, c, heads=heads, num_layers=1)
    o = c if last else heads * c
    bias, skip_w, skip_b = (
        jnp.asarray(rng.normal(size=s), jnp.float32) for s in [(o,), (d, o), (o,)]
    )
    layer = p.layers[0]._replace(bias=bias, skip_w=skip_w, skip_b=skip_b)
    got = gat._layer(layer, h, last)
    want = _published_layer(layer, h, last)
    assert [x.shape for x in got] == [x.shape for x in want] == [(b, o), (b * fanout, o)]
    # float32 with full-precision products on the CPU against float64:
    # a few float32 roundings (6e-8 each) over sums of at most 20 terms.
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), r, rtol=1e-5, atol=1e-5 * np.abs(r).max())


def _layer_inputs(seed, d=6, n=5, f=3):
    """Targets ``(n, d)`` and their children laid out fanout first,
    ``(f * n, d)``: row ``j * n + i`` is target i's j-th child."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, d)), jnp.float32), jnp.asarray(
        rng.normal(size=(f * n, d)), jnp.float32
    )


def _one_layer(seed, last, d=6, heads=3, c=4):
    p = gat.init_gat(jax.random.PRNGKey(seed), d, c, c, heads=heads, num_layers=1)
    layer = p.layers[0]
    o = c if last else heads * c
    return layer._replace(
        bias=jnp.zeros((o,)), skip_w=jnp.zeros((d, o)), skip_b=jnp.zeros((o,))
    )


@pytest.mark.parametrize("last", [False, True])
def test_equal_logits_give_the_mean_over_neighbours_and_self(last):
    heads, c = 3, 4
    layer = _one_layer(0, last)._replace(
        att_src=jnp.zeros((heads, c)), att_dst=jnp.zeros((heads, c))
    )
    tgt, kids = _layer_inputs(1)
    n = len(tgt)
    (out,) = gat._layer(layer, [tgt, kids], last)
    wx = lambda x: np.asarray(x @ layer.w, np.float64).reshape(len(x), heads, c)  # noqa: E731
    mean = (wx(tgt) + wx(kids).reshape(-1, n, heads, c).sum(0)) / (1 + len(kids) // n)
    expect = mean.mean(1) if last else mean.reshape(n, heads * c)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-6)


def test_last_layer_averages_its_heads():
    heads, c = 3, 4
    concat, last = _one_layer(2, False), _one_layer(2, True)
    tgt, kids = _layer_inputs(3)
    (a,) = gat._layer(concat, [tgt, kids], False)
    (b,) = gat._layer(last, [tgt, kids], True)
    assert a.shape == (len(tgt), heads * c) and b.shape == (len(tgt), c)
    np.testing.assert_allclose(
        np.asarray(b), np.asarray(a).reshape(-1, heads, c).mean(1), rtol=1e-6, atol=1e-7
    )


def test_published_widths_have_the_published_parameter_count():
    # OGB's "GAT w/NS" for ogbn-products: F = 100, 3 layers of 4 heads x
    # 128 channels, 47 classes: 751,574 parameters.
    params = jax.eval_shape(lambda: gat.init_gat(jax.random.PRNGKey(0), 100, 128, 47))
    leaves = jax.tree_util.tree_leaves(params)
    assert len(leaves) == 18
    assert sum(x.size for x in leaves) == 751_574


def _two_hop_blocks(minibatch):
    """``index_blocks`` as it was written for two hops."""
    b, f1 = minibatch.layer_nbrs[0].shape
    return (
        minibatch.seeds.astype(np.int32),
        minibatch.layer_nbrs[0].astype(np.int32),
        minibatch.layer_nbrs[1].reshape(b, f1, -1).astype(np.int32),
        minibatch.labels.astype(np.int32),
    )


@pytest.fixture(scope="module")
def parts():
    return partition_graph(generate("products", seed=0, scale=0.1), 4)


@pytest.mark.parametrize("fanouts", [(3, 5), (3, 2, 2)])
def test_index_blocks_has_one_block_per_hop(parts, fanouts):
    t = DistributedTrainer(parts, fanouts=fanouts, train_model=False, variant="fixed")
    mb = t.sampler.sample(t._seed_batch(0, 0, 0), np.random.default_rng(3))
    blocks = index_blocks(mb)
    B = len(mb.seeds)
    shapes = [(B,)]
    for f in fanouts:
        shapes.append(shapes[-1] + (f,))
    assert [x.shape for x in blocks] == shapes + [(B,)]
    assert all(x.dtype == np.int32 for x in blocks)
    for k, x in enumerate(blocks[1:-1]):
        np.testing.assert_array_equal(x.reshape(-1, fanouts[k]), mb.layer_nbrs[k])
    if len(fanouts) == 2:
        for new, old in zip(blocks, _two_hop_blocks(mb)):
            assert new.shape == old.shape and new.tobytes() == old.tobytes()


@pytest.mark.parametrize(
    "kw, match",
    [
        (dict(model="sage", heads=4), "heads"),
        (dict(model="sage", fanouts=(3, 2, 2)), "2 layers"),
        (dict(model="gat", fanouts=(3,)), "at least 2"),
        (dict(model="gat", heads=0), "heads"),
        (dict(model="gcn"), "model must be"),
    ],
)
def test_trainer_refuses(parts, kw, match):
    with pytest.raises(ValueError, match=match):
        DistributedTrainer(parts, variant="fixed", **kw)


def test_gat_trains_alike_on_every_runtime(parts):
    kw = dict(
        variant="fixed", epochs=1, batch_size=16, fanouts=(3, 2, 2), hidden_dim=8,
        buffer_frac=0.25, interval=4, model="gat", heads=2,
    )
    runs = [
        DistributedTrainer(parts, **kw, **path).run()
        for path in (dict(), dict(runtime="legacy"), dict(device="jnp"))
    ]
    assert len(runs[0].losses) > 1 and all(np.isfinite(runs[0].losses))
    assert runs[0].losses[-1] < runs[0].losses[0]
    for r in runs[1:]:
        assert r.losses == runs[0].losses and r.accuracy == runs[0].accuracy
