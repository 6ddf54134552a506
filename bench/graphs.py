"""The configuration's graph, made by the benchmark from its numbers.

A configuration states a public graph's published numbers (nodes,
undirected edges, feature width, classes, train nodes) and the shape of
its twin (``twin``: community size, the share of edges inside a
community, the Zipf skew of degrees within a community, label and
feature noise). :func:`make` draws a degree-corrected stochastic block
model with exactly that many nodes and distinct undirected edges, so the
mean degree is the published one, from ``graph_seed``; labels follow
communities and features are a class centroid plus noise, as in the
program's own presets. The draw is vectorised over all edges: an intra
community endpoint is drawn by inverting the cumulative weights inside
its community, so the cost grows as edges x log(nodes).

:func:`partitioned` hands the arrays to the program's ``Graph`` and
partitioner, keeps the result in ``<bench>/.cache/graphs`` keyed by the
configuration's graph numbers and the code that makes them, and adds
each partition's halo size (distinct one-hop neighbours homed
elsewhere), which the accounting reference sizes its buffers from.
"""

from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path

import numpy as np

#: The keys of a configuration that define its graph.
GRAPH_KEYS = ("num_nodes", "num_edges", "feature_dim", "num_classes",
              "train_nodes", "graph_seed", "twin")


def _community_of(n: int, size: int, rng) -> tuple[np.ndarray, np.ndarray]:
    """Sorted community of each node and each community's first node."""
    k = max(16, n // size)
    comm = np.sort(rng.integers(0, k, size=n)).astype(np.int32)
    starts = np.searchsorted(comm, np.arange(k + 1)).astype(np.int64)
    return comm, starts


def _weights(comm: np.ndarray, starts: np.ndarray, zipf_s: float, rng) -> np.ndarray:
    """Zipf weight of each node by a random rank within its community."""
    n = len(comm)
    order = np.lexsort((rng.random(n), comm))
    rank = np.empty(n, np.float64)
    rank[order] = np.arange(n) - starts[comm[order]] + 1
    return rank ** (-zipf_s)


def _inverse(cum, u) -> np.ndarray:
    """Node of each point ``u`` on the cumulative weights. The points are
    looked up in sorted order, which is several times faster, and each
    node is put back where its point was."""
    order = np.argsort(u)
    out = np.empty(len(u), np.int64)
    out[order] = np.searchsorted(cum, u[order], side="right")
    return np.minimum(out, len(cum) - 1, out=out)


def _draw(k: int, cum, comm, starts, intra_prob: float, rng) -> np.ndarray:
    """``k`` undirected edges as keys ``min * n + max``, self loops dropped.

    One end is drawn by weight over all nodes; the other by weight
    within the first end's community with probability ``intra_prob``,
    else over all nodes."""
    n = len(comm)
    total = cum[-1]
    src = _inverse(cum, rng.random(k) * total)
    intra = rng.random(k) < intra_prob
    c = comm[src[intra]]
    lo, hi = starts[c], starts[c + 1]
    base = np.where(lo > 0, cum[np.maximum(lo - 1, 0)], 0.0)
    u = rng.random(k)
    u[~intra] *= total
    u[intra] = base + u[intra] * (cum[hi - 1] - base)
    dst = _inverse(cum, u)
    dst[intra] = np.clip(dst[intra], lo, hi - 1)
    keep = src != dst
    a, b = src[keep], dst[keep]
    return np.minimum(a, b) * n + np.maximum(a, b)


def edge_keys(n: int, m: int, twin: dict, rng) -> tuple[np.ndarray, np.ndarray]:
    """Exactly ``m`` distinct undirected edges, sorted, and the communities.

    Edges are drawn in rounds until ``m`` distinct ones are held; each
    round draws the shortfall over the last round's share of new edges.
    The surplus of the last round is dropped at random."""
    comm, starts = _community_of(n, int(twin["community_size"]), rng)
    cum = np.cumsum(_weights(comm, starts, float(twin["zipf_s"]), rng))
    if m > n * (n - 1) // 2:
        raise ValueError(f"{m} edges cannot be distinct among {n} nodes")
    keys = np.zeros(0, np.int64)
    new_share = 1.0
    for _ in range(64):
        short = m - len(keys)
        if short <= 0:
            break
        drawn = _draw(int(short / new_share * 1.02) + 64, cum, comm, starts,
                      float(twin["intra_prob"]), rng)
        before = len(keys)
        keys = np.union1d(keys, drawn)
        new_share = max((len(keys) - before) / len(drawn), 1e-3)
    else:
        raise RuntimeError(f"only {len(keys)} of {m} distinct edges after 64 rounds")
    if len(keys) > m:
        keys = np.sort(keys[rng.choice(len(keys), size=m, replace=False)])
    return keys, comm


def csr(n: int, keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Both directions of every edge, as CSR with sorted neighbours."""
    a, b = np.divmod(keys, n)
    both = np.concatenate([keys, b * n + a])
    both.sort()
    rows, cols = np.divmod(both, n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return indptr, cols


def make(config: dict) -> dict:
    """The graph's arrays and numbers, from the configuration alone."""
    n, m = int(config["num_nodes"]), int(config["num_edges"])
    F, C = int(config["feature_dim"]), int(config["num_classes"])
    twin = config["twin"]
    rng = np.random.default_rng(int(config["graph_seed"]))
    keys, comm = edge_keys(n, m, twin, rng)
    indptr, indices = csr(n, keys)
    del keys
    labels = (comm % C).astype(np.int32)
    flip = rng.random(n) < float(twin["label_noise"])
    labels[flip] = rng.integers(0, C, size=int(flip.sum()))
    centroids = rng.standard_normal((C, F), dtype=np.float32)
    features = rng.standard_normal((n, F), dtype=np.float32)
    features *= np.float32(twin["feature_noise"])
    features += centroids[labels]
    train = np.sort(rng.choice(n, size=int(config["train_nodes"]), replace=False))
    return {
        "indptr": indptr,
        "indices": indices,
        "features": features,
        "labels": labels,
        "train_nodes": train.astype(np.int64),
        "communities": comm,
    }


def halo_sizes(indptr, indices, part_of, num_parts: int) -> np.ndarray:
    """Distinct one-hop neighbours of each partition homed elsewhere."""
    src_part = np.repeat(part_of, np.diff(indptr))
    out = []
    for p in range(num_parts):
        nbrs = np.unique(indices[src_part == p])
        out.append(int((part_of[nbrs] != p).sum()))
    return np.array(out, np.int64)


def _digest(config: dict, trainers: int) -> str:
    import repro.graph

    h = hashlib.sha256(json.dumps(
        [{k: config[k] for k in GRAPH_KEYS}, trainers], sort_keys=True
    ).encode())
    h.update(Path(__file__).read_bytes())
    for path in sorted(Path(repro.graph.__file__).parent.glob("*.py")):
        h.update(path.read_bytes())
    return h.hexdigest()[:16]


def partitioned(config: dict, cache_dir: Path):
    """The program's partitioned graph for ``config`` and each
    partition's halo size, from the cache once made."""
    from repro.graph import Graph, partition_graph
    from repro.graph.partition import Partitioned

    P = int(config["trainers"])
    path = cache_dir / f"{config['name']}-p{P}-{_digest(config, P)}.npz"
    if path.exists():
        with np.load(path, allow_pickle=False) as z:
            a = {k: z[k] for k in z.files}
        local = np.split(a.pop("local_nodes"), np.cumsum(a.pop("local_counts"))[:-1])
        part_of, edge_cut, halos = a.pop("part_of"), int(a.pop("edge_cut")), a.pop("halos")
        graph = Graph(name=config["name"], num_classes=int(config["num_classes"]), **a)
        return Partitioned(graph, P, part_of, local, edge_cut), halos
    a = make(config)
    graph = Graph(name=config["name"], num_classes=int(config["num_classes"]), **a)
    parts = partition_graph(graph, P)
    halos = halo_sizes(graph.indptr, graph.indices, parts.part_of, P)
    cache_dir.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(f".{os.getpid()}.tmp.npz")
    np.savez(
        tmp,
        **a,
        part_of=parts.part_of,
        edge_cut=np.int64(parts.edge_cut),
        halos=halos,
        local_nodes=np.concatenate(parts.local_nodes),
        local_counts=np.array([len(x) for x in parts.local_nodes]),
    )
    os.replace(tmp, path)
    return parts, halos
