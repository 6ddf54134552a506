"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip: the result line has the contract's keys and nothing else."""

from __future__ import annotations

import json
import time

import pytest

from bench import harness, peaks

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.fixture
def cpu_peaks(monkeypatch):
    # Test-only peaks, so the device readers run on the CPU.
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.Peak(1e12, 1e11, 1e10, "test"))


@pytest.mark.parametrize("trace", [False, True])
def test_result_line(tiny_root, graph_cache, cpu_peaks, trace):
    out = harness.run_cell(
        tiny_root, "tiny-sage.rudder-async", 2**31 + 5, 1.0, trace,
        time.perf_counter(), cache_dir=graph_cache,
    )
    line = json.loads(json.dumps(out))
    assert set(line) == (KEYS | {"breakdown"} if trace else KEYS)
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    names = set(line["metrics"])
    if trace:
        assert {"sample_ms", "train_ms", "fetch_ms", "buffer_hit_pct"} <= names
        assert {"busy_s", "window_s"} <= set(line["device"])
    else:
        assert names == {"seeds_per_s", "setup_s"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


def test_same_seed_same_inputs(tiny_root, graph_cache):
    cell = harness.resolve_cell(tiny_root, harness.load_spec(tiny_root), "tiny-sage.distdgl")
    a, b = (harness.build(cell, 77, graph_cache) for _ in range(2))
    for job in (a, b):
        harness.warm_up(job)
    for x, y in zip(a.spy.steps(0, 3), b.spy.steps(0, 3)):
        for u, v in zip(x, y):
            assert all((p == q).all() for p, q in zip(u, v))
    assert a.runs[0].losses == b.runs[0].losses
