"""Shared fixtures: a throwaway benchmark tree with one tiny cell."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: A products twin small enough for the CPU: 2,880 nodes, 4 trainers,
#: batch 16, fanout (3, 4), hidden 16; the correctness limits are the
#: products-sage cell's.
TINY = {
    "name": "tiny-sage",
    "source": "https://ogb.stanford.edu/docs/nodeprop/#ogbn-products",
    "model": "sage",
    "num_nodes": 2880,
    "num_edges": 36000,
    "feature_dim": 100,
    "num_classes": 47,
    "train_nodes": 230,
    "graph_seed": 0,
    "twin": {"community_size": 180, "intra_prob": 0.92, "zipf_s": 0.85,
             "label_noise": 0.1, "feature_noise": 0.6},
    "trainers": 4,
    "trainer": {"hidden_dim": 16, "fanouts": [3, 4], "lr": 0.01},
}


def write_tiny_tree(dest: Path, mixes=("rudder-async", "distdgl")) -> Path:
    """A copy of the benchmark's files under ``dest`` whose
    ``BENCHMARK.json`` has one tiny cell per mix; returns ``dest``."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(
        ROOT / "bench", dest / "bench", ignore=shutil.ignore_patterns(".cache", "__pycache__")
    )
    products = json.loads((ROOT / "bench/configs/products-sage.json").read_text())
    tiny = dict(TINY, limits=products["limits"])
    (dest / "bench/configs/tiny-sage.json").write_text(json.dumps(tiny))
    spec["configs"] = [
        {"name": "tiny-sage", "source": TINY["source"], "file": "bench/configs/tiny-sage.json",
         "reduced": ["num_nodes"], "why": "tests"}
    ]
    spec["workloads"] = [
        {"name": f"tiny-sage.{m}", "config": "tiny-sage", "traffic": m, "chips": 1, "why": "tests"}
        for m in mixes
    ]
    for m in spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-sage.rudder-async"]
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    for name in mixes:
        mix = json.loads((ROOT / f"bench/traffic/{name}.json").read_text())
        mix["trainer"]["batch_size"] = 16
        (dest / f"bench/traffic/{name}.json").write_text(json.dumps(mix))
    return dest


@pytest.fixture(scope="session")
def graph_cache(tmp_path_factory) -> Path:
    return tmp_path_factory.mktemp("graphs")


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    return write_tiny_tree(tmp_path)
