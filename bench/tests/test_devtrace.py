"""The profiler-trace reduction, on hand-made events and on a trace
the JAX profiler records here."""

from __future__ import annotations

import numpy as np
import pytest

from bench import devtrace


def test_host_segments_name_the_innermost_span():
    spans = [("step", 0, 10), ("sample", 0, 2), ("train", 5, 9), ("fetch.probe", 2, 3)]
    assert devtrace.host_segments(spans) == [
        (0, 2, "sample"), (2, 3, "fetch.probe"), (3, 5, "step"), (5, 9, "train"), (9, 10, "step"),
    ]


def test_idle_by_host_splits_gaps_at_span_edges():
    trace = {"window": [0, 10], "devices": {"/device:TPU:0": {
        "ops": [["a", 1, 1], ["b", 6, 2]], "modules": []}}}
    seg = devtrace.host_segments([("step", 0, 9), ("train", 5, 9)])
    idle = dict(devtrace.idle_by_host(trace, seg))
    # Idle: [0,1] [2,5] step, [5,6] train, [8,9] train, [9,10] outside.
    assert idle["step"] == pytest.approx(4e-9)
    assert idle["train"] == pytest.approx(2e-9)
    assert idle[devtrace.NO_SPAN] == pytest.approx(1e-9)
    assert devtrace.busy_s(trace) == pytest.approx(3e-9)


def test_clipping_to_the_window():
    trace = {"window": [10, 20], "devices": {"d": {"ops": [["x", 5, 10], ["y", 18, 5]],
                                                    "modules": [["m", 5, 10], ["m", 12, 3]]}}}
    assert devtrace.busy_s(trace) == pytest.approx(7e-9)
    assert devtrace.module_time(trace, "m") == (pytest.approx(3e-9), 1)
    assert devtrace.window_s(trace) == pytest.approx(1e-8)


def test_reads_a_recorded_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    trace = devtrace.read_xplane(str(tmp_path), "bench.window")
    t0, t1 = trace["window"]
    assert t1 > t0 >= 0
    # The CPU backend has no device plane: nothing to call busy.
    assert trace["devices"] == {} and devtrace.busy_s(trace) is None
    with pytest.raises(RuntimeError):
        devtrace.read_xplane(str(tmp_path), "no.such.annotation")


def test_union_of_nothing():
    assert devtrace.union(np.zeros((0, 2))).shape == (0, 2)


def test_recorded_tpu_trace():
    """The first 1.2 s of a products-sage.distdgl window traced on a TPU
    v5e: the reduction against a brute-force count at one-microsecond
    grain."""
    import json
    from pathlib import Path

    trace = json.loads((Path(__file__).parent / "data/tpu_trace.json").read_text())
    t0, t1 = trace["window"]
    ops = trace["devices"]["/device:TPU:0"]["ops"]
    grid = np.zeros(int((t1 - t0) // 1000) + 1, bool)
    for _, s, d in ops:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            grid[int((a - t0) // 1000) : int(np.ceil((b - t0) / 1000))] = True
    assert devtrace.busy_s(trace) == pytest.approx(grid.sum() * 1e-6, rel=0.02)
    mods = [m for m in trace["devices"]["/device:TPU:0"]["modules"]
            if "sage_grads" in m[0] and t0 <= m[1] and m[1] + m[2] <= t1]
    assert devtrace.module_time(trace, "sage_grads") == (
        pytest.approx(sum(m[2] for m in mods) * 1e-9), len(mods))
    # Sampling, then one execution per trainer, about 0.35 s apart (the
    # host gathers and uploads each trainer's features in between).
    assert len(mods) == 3
    top = devtrace.top_ops(trace)
    assert top[0][0] == "jit_sage_grads/reduce_sum.94"
    assert len(top) == 10 and all(t > 0 for _, t in top)
