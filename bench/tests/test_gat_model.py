"""The GAT model module (``bench/models/gat.py``): it reproduces its
recorded reference, its work counts match a hand count at the cell's
shapes, and the program's ``DistributedTrainer(model="gat")``, driven
through ``run()`` on the tiny graph on either runtime, follows it."""

from __future__ import annotations

import json
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import ROOT, TINY, write_tiny_tree

from bench import check, graphs, harness, reference
from bench.models import gat

#: ``data/gat_golden.npz`` holds the weights from ``SEED``, then the
#: losses, the first step's mean gradient and the weights after each of
#: ``STEPS`` steps that the GAT reference gave on ``golden_steps``,
#: recorded from this module when it was written.
GOLDEN = Path(__file__).parent / "data/gat_golden.npz"
SEED = 2**31 + 9
B, FANOUTS, HEADS, HIDDEN, P, STEPS, LR = 16, (3, 2, 2), 2, 8, 4, 3, 0.01
TRAINER = {"model": "gat", "heads": HEADS, "hidden_dim": HIDDEN, "fanouts": list(FANOUTS),
           "lr": LR}


def golden_steps(labels: np.ndarray, seed: int) -> list:
    """``STEPS`` steps of ``P`` trainers' minibatches of random node ids
    at the tiny GAT cell's batch and fanouts."""
    rng = np.random.default_rng(seed)
    n = len(labels)
    steps = []
    for _ in range(STEPS):
        batches = []
        for _ in range(P):
            seeds = rng.integers(0, n, B)
            hops, rows = [], B
            for f in FANOUTS:
                hops.append(rng.integers(0, n, (rows, f)))
                rows *= f
            batches.append((seeds, hops, labels[seeds]))
        steps.append(batches)
    return steps


def test_gat_reproduces_its_recorded_reference():
    gold = np.load(GOLDEN)
    g = graphs.make(TINY)
    s = gat.shapes(TINY, dict(TRAINER, batch_size=B))
    w0 = gat.init_weights(SEED, s)
    assert set(w0) == set(gat.LEAVES)
    for k in gat.LEAVES:
        assert np.array_equal(np.asarray(w0[k]), gold[f"w0/{k}"]), k
    losses, grads1, after = gat.train(
        w0, reference.device_table(g["features"]), golden_steps(g["labels"], SEED), LR
    )
    assert losses == gold["losses"].tolist()
    for k in gat.LEAVES:
        assert np.array_equal(grads1[k], gold[f"grads1/{k}"]), k
    assert len(after) == STEPS
    for t, w in enumerate(after):
        for k in gat.LEAVES:
            assert np.array_equal(w[k], gold[f"after{t}/{k}"]), (t, k)


def test_counts_match_hand_count_at_the_cells_shapes():
    s = gat.Shapes(batch=512, fanouts=(10, 10, 10), feature_dim=100, hidden=128, heads=4,
                   classes=47)
    # 751,574 parameters (PyG's count of the published model).
    assert s.params == 751_574
    # Rows per hop: 512, 5,120, 51,200, 512,000; 568,832 in all.
    # Layer 1 reads all 568,832 rows (F = 100) and writes 56,832 targets
    # with 625,152 edges (10 children and the self loop each), 4 x 128.
    # Reordered, forward: scores (568,832 + 56,832) x 2 x 100 x 4 =
    # 500,531,200; row sums 625,152 x 2 x 100 x 4 = 500,121,600;
    # projections 56,832 x 2 x 100 x 512 = 5,819,596,800; skip as many;
    # W^T a 4 x 100 x 512 = 204,800. Published, forward: projections
    # 568,832 x 2 x 100 x 512 = 58,248,396,800, scores (568,832 + 56,832)
    # x 2 x 512 = 640,679,936, sums 625,152 x 2 x 512 = 640,155,648, skip.
    l1_re = 500_531_200 + 500_121_600 + 2 * 5_819_596_800 + 204_800
    l1_pub = 58_248_396_800 + 640_679_936 + 640_155_648 + 5_819_596_800
    # Layer 2: 56,832 rows of 512 in, 5,632 targets, 61,952 edges.
    l2_re = ((56_832 + 5_632) * 2 * 512 * 4 + 61_952 * 2 * 512 * 4
             + 2 * 5_632 * 2 * 512 * 512 + 4 * 512 * 512)
    l2_pub = (56_832 * 2 * 512 * 512 + (56_832 + 5_632) * 2 * 512 + 61_952 * 2 * 512
              + 5_632 * 2 * 512 * 512)
    # Layer 3: 5,632 rows in, 512 targets, 5,632 edges, 4 x 47, skip to 47.
    l3_re = ((5_632 + 512) * 2 * 512 * 4 + 5_632 * 2 * 512 * 4 + 512 * 2 * 512 * 188
             + 512 * 2 * 512 * 47 + 4 * 512 * 188)
    l3_pub = (5_632 * 2 * 512 * 188 + (5_632 + 512) * 2 * 188 + 5_632 * 2 * 188
              + 512 * 2 * 512 * 47)
    # Forward: about 19 GFLOP reordered, about 99 as published.
    assert round((l1_re + l2_re + l3_re) / 1e9, 2) == 19.23
    assert round((l1_pub + l2_pub + l3_pub) / 1e9, 1) == 99.3
    # With the backward: layer 1's scores, sums and skip read the
    # features, which take no gradient (x2); its projections and every
    # product of layers 2 and 3 take gradients on both sides (x3).
    l1 = 2 * (500_531_200 + 500_121_600 + 5_819_596_800) + 3 * (5_819_596_800 + 204_800)
    assert gat.layer_flops(s)[0] == (
        2 * 58_248_396_800 + 3 * (640_679_936 + 640_155_648) + 2 * 5_819_596_800, l1
    )
    assert gat.grads_flops(s) == l1 + 3 * (l2_re + l3_re)
    # Bytes: 568,832 int32 ids, their 568,832 x 100 float32 features, 512
    # labels, the weights read and the gradients written, 1 loss.
    assert gat.grads_bytes(s) == 4 * (568_832 + 56_883_200 + 512 + 2 * 751_574 + 1)


def _gat_tree(dest: Path, runtime: str | None = None) -> Path:
    """A benchmark tree whose cell ``tiny-gat.distdgl`` trains GAT on the
    tiny graph, with the products-gat cell's limits."""
    root = write_tiny_tree(dest, mixes=("distdgl",))
    products = json.loads((ROOT / "bench/configs/products-gat.json").read_text())
    cfg = dict(TINY, name="tiny-gat", model="gat", trainer=TRAINER, limits=products["limits"])
    (root / "bench/configs/tiny-gat.json").write_text(json.dumps(cfg))
    if runtime is not None:
        mix = json.loads((root / "bench/traffic/distdgl.json").read_text())
        mix["trainer"]["runtime"] = runtime
        (root / "bench/traffic/distdgl.json").write_text(json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"] = [dict(spec["configs"][0], name="tiny-gat", file="bench/configs/tiny-gat.json")]
    spec["workloads"] = [{"name": "tiny-gat.distdgl", "config": "tiny-gat", "traffic": "distdgl",
                          "chips": 1, "why": "tests"}]
    for m in spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-gat.distdgl"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture
def gat_root(tmp_path) -> Path:
    return _gat_tree(tmp_path)


def _job(root, graph_cache, seed):
    cell = harness.resolve_cell(root, harness.load_spec(root), "tiny-gat.distdgl")
    job = harness.build(cell, seed, graph_cache)
    harness.warm_up(job)
    job.run()
    return job


def test_trainer_follows_the_reference_on_either_runtime(tmp_path, graph_cache):
    job = _job(_gat_tree(tmp_path / "staged"), graph_cache, 2**31 + 3)
    readings = harness.readings(job)
    # Program and reference are both float32 at full precision on the
    # CPU; they differ by summation order only (tests/test_gat.py).
    for name in ("loss_gap", "grad_gap", "update_gap", "window_loss_gap", "window_update_gap"):
        assert readings[name] < 1e-4, (name, readings[name])
    assert readings["steps_unchanged"] == readings["accounting_mismatches"] == 0
    # The legacy runtime draws the same minibatches and trains the same
    # steps, bit for bit, from the same weights.
    legacy = _job(_gat_tree(tmp_path / "legacy", runtime="legacy"), graph_cache, 2**31 + 3)
    assert legacy.trainer.runtime == "legacy" and job.trainer.runtime == "vectorized"
    for a, b in zip(job.runs, legacy.runs):
        assert a.losses == b.losses
    for a, b in zip(harness._leaves(job.trainer.params, gat.LEAVES).values(),
                    harness._leaves(legacy.trainer.params, gat.LEAVES).values()):
        assert np.array_equal(a, b)


def test_sound_gat_program_is_correct(gat_root, graph_cache):
    out = harness.run_cell(gat_root, "tiny-gat.distdgl", 11, 0.5, False, time.perf_counter(),
                           graph_cache)
    assert out["correct"] is True


def test_bfloat16_control_is_not_correct(gat_root, graph_cache):
    job = _job(gat_root, graph_cache, 5)
    limits = json.loads((gat_root / "bench/configs/tiny-gat.json").read_text())["limits"]
    table = reference.device_table(job.parts.graph.features)
    _, window_at = harness.followed_steps(job)
    ref = harness.reference_run(job, table)
    losses, _, after = harness.reference_run(job, table, dtype=jnp.bfloat16)
    readings = check.training_readings(job.w0, losses, after, *ref, job.lr, window_at)
    readings.update(steps_unchanged=0, accounting_mismatches=0)
    assert not check.verdict(readings, limits)[0]
