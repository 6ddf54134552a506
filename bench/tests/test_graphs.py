"""The benchmark's graph has the configuration's numbers exactly, and
the same graph seed gives the same graph."""

from __future__ import annotations

import numpy as np
from conftest import TINY

from bench import graphs


def test_graph_has_the_configured_numbers():
    a = graphs.make(TINY)
    n, m = TINY["num_nodes"], TINY["num_edges"]
    indptr, indices = a["indptr"], a["indices"]
    assert len(indptr) == n + 1 and len(indices) == 2 * m == indptr[-1]
    rows = np.repeat(np.arange(n), np.diff(indptr))
    assert (rows != indices).all()
    fwd = rows * n + indices
    assert len(np.unique(fwd)) == 2 * m
    assert np.array_equal(np.sort(fwd), np.sort(indices * n + rows))
    assert a["features"].shape == (n, TINY["feature_dim"])
    assert a["features"].dtype == np.float32
    assert a["labels"].max() < TINY["num_classes"]
    assert len(np.unique(a["train_nodes"])) == TINY["train_nodes"]


def test_same_graph_seed_same_graph():
    a, b = graphs.make(TINY), graphs.make(TINY)
    for k in a:
        assert np.array_equal(a[k], b[k]), k
    c = graphs.make(dict(TINY, graph_seed=1))
    assert not np.array_equal(a["indices"], c["indices"])


def test_halo_sizes_count_distinct_remote_neighbours():
    # 0-1 and 1-2 and 2-3, partitions {0, 1} and {2, 3}: each has one.
    indptr = np.array([0, 1, 3, 5, 6])
    indices = np.array([1, 0, 2, 1, 3, 2])
    part_of = np.array([0, 0, 1, 1])
    assert graphs.halo_sizes(indptr, indices, part_of, 2).tolist() == [1, 1]
