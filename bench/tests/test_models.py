"""A configuration brings its own model: the harness finds the model
module by the configuration's ``model`` key, hands its ``train`` every
hop the sampler drew, and refuses a configuration whose model it cannot
find; and the GraphSAGE module reproduces its recorded reference."""

from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest
from conftest import TINY, write_tiny_tree

from bench import graphs, harness, reference
from bench.models import sage
from repro.graph.sampler import SamplerPlane

# --------------------------------------------------------------------- #
# GraphSAGE against its recorded numbers
# --------------------------------------------------------------------- #
#: ``data/sage_golden.npz`` holds the weights from ``SEED``, then the
#: losses, the first step's mean gradient and the weights after each of
#: ``STEPS`` steps that the GraphSAGE reference gave on the steps of
#: ``golden_steps``, recorded when it lived in ``bench/reference.py``.
GOLDEN = Path(__file__).parent / "data/sage_golden.npz"
SEED = 2**31 + 5
B, F1, F2, P, STEPS, LR = 16, 3, 4, 4, 3, 0.01


def golden_steps(labels: np.ndarray, seed: int) -> list:
    """``STEPS`` steps of ``P`` trainers' minibatches of random node ids
    at the tiny cell's batch and fanouts."""
    rng = np.random.default_rng(seed)
    n = len(labels)
    steps = []
    for _ in range(STEPS):
        batches = []
        for _ in range(P):
            seeds = rng.integers(0, n, B)
            hops = [rng.integers(0, n, (B, F1)), rng.integers(0, n, (B * F1, F2))]
            batches.append((seeds, hops, labels[seeds]))
        steps.append(batches)
    return steps


def test_sage_reproduces_its_recorded_reference():
    gold = np.load(GOLDEN)
    g = graphs.make(TINY)
    s = sage.shapes(TINY, dict(TINY["trainer"], batch_size=B))
    assert (s.batch, s.fanouts, s.hidden) == (B, (F1, F2), 16)
    w0 = sage.init_weights(SEED, s)
    assert set(w0) == set(sage.LEAVES)
    for k in sage.LEAVES:
        assert np.array_equal(np.asarray(w0[k]), gold[f"w0/{k}"]), k
    losses, grads1, after = sage.train(
        w0, reference.device_table(g["features"]), golden_steps(g["labels"], SEED), LR
    )
    assert losses == gold["losses"].tolist()
    for k in sage.LEAVES:
        assert np.array_equal(grads1[k], gold[f"grads1/{k}"]), k
    assert len(after) == STEPS
    for t, w in enumerate(after):
        for k in sage.LEAVES:
            assert np.array_equal(w[k], gold[f"after{t}/{k}"]), (t, k)


# --------------------------------------------------------------------- #
# A new model found by name
# --------------------------------------------------------------------- #
TOY = '''"""A toy model: a linear map of the mean of every hop's features.
Trained by no program; it records the minibatches its train is given."""

from dataclasses import dataclass

import numpy as np

PROGRAM = "toy_grads"
LEAVES = ("w",)
SEEN = []


@dataclass(frozen=True)
class Shapes:
    feature_dim: int
    classes: int
    hops: int


def shapes(config, trainer_kwargs):
    return Shapes(config["feature_dim"], config["num_classes"], len(trainer_kwargs["fanouts"]))


def init_weights(seed, s):
    return {"w": np.random.default_rng(seed).normal(size=(s.feature_dim, s.classes))}


def train(w0, table, steps, lr, dtype=np.float32, *, fault=None):
    table = np.asarray(table, np.float64)
    losses = []
    for batches in steps:
        SEEN.append(batches)
        x = [np.mean([table[h.reshape(-1)].mean(0) for h in hops], 0) for _, hops, _ in batches]
        losses.append(float(np.mean(np.asarray(x) @ w0["w"])))
    return losses, {"w": np.zeros_like(w0["w"])}, [dict(w0) for _ in steps]


def grads_flops(s):
    return 2.0 * s.feature_dim * s.classes


def grads_bytes(s):
    return 4.0 * s.feature_dim * s.classes
'''


def test_a_new_model_is_found_by_name_and_given_every_hop(tmp_path, graph_cache):
    root = write_tiny_tree(tmp_path)
    sources = {p: (root / p).read_bytes() for p in ("bench/harness.py", "bench/check.py")}
    bench = root / "bench"
    (bench / "models/toy.py").write_text(TOY)
    cfg = json.loads((bench / "configs/tiny-sage.json").read_text())
    cfg.update(name="tiny-toy", model="toy", trainer=dict(cfg["trainer"], fanouts=[3, 2, 2]))
    (bench / "configs/tiny-toy.json").write_text(json.dumps(cfg))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="tiny-toy",
                                file="bench/configs/tiny-toy.json"))
    spec["workloads"].append({"name": "tiny-toy.distdgl", "config": "tiny-toy",
                              "traffic": "distdgl", "chips": 1, "why": "new model"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.resolve_cell(root, harness.load_spec(root), "tiny-toy.distdgl")
    toy = cell.model
    assert Path(toy.__file__) == bench / "models/toy.py"
    kw = harness._trainer_kwargs(cell)
    s = toy.shapes(cell.config, kw)
    assert s.hops == 3
    # The sampler's three-hop minibatches, recorded by the harness's spy
    # over a warm-up of 4 steps and a window of 3, as a run records them.
    parts, halos = graphs.partitioned(cell.config, graph_cache)
    stand_in = SimpleNamespace(
        sampler_plane=SamplerPlane(parts.graph, kw["fanouts"]), params=toy.init_weights(5, s)
    )
    spy = harness.SamplerSpy(stand_in)
    rng = np.random.default_rng(5)
    blocks = [parts.graph.train_nodes[p::4][:8] for p in range(4)]
    for n in (4, 3):
        spy.begin()
        for _ in range(n):
            spy.sample_all(blocks, rng)
        spy.end()
    job = harness.Job(parts, halos, stand_in, spy, stand_in.params, toy, s, kw, 4, False)
    losses, _, after = harness.reference_run(job)

    assert len(losses) == len(after) == len(toy.SEEN) == 7
    for batches in toy.SEEN:
        assert len(batches) == 4
        for seeds, hops, labels in batches:
            assert len(seeds) == len(labels) == 8
            assert [h.shape for h in hops] == [(8, 3), (24, 2), (48, 2)]
    assert all((root / p).read_bytes() == b for p, b in sources.items())


@pytest.mark.parametrize("model", [None, "no_such_model"])
def test_a_missing_model_names_the_path(tmp_path, model):
    root = write_tiny_tree(tmp_path)
    path = root / "bench/configs/tiny-sage.json"
    cfg = json.loads(path.read_text())
    if model is None:
        del cfg["model"]
        expect = path
    else:
        cfg["model"] = model
        expect = root / "bench/models/no_such_model.py"
    path.write_text(json.dumps(cfg))
    with pytest.raises((ValueError, FileNotFoundError), match="model") as err:
        harness.resolve_cell(root, harness.load_spec(root), "tiny-sage.distdgl")
    assert str(expect) in str(err.value)
