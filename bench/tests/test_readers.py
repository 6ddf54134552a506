"""Every metric reader on a small recorded telemetry session and trace."""

from __future__ import annotations

import math
from types import SimpleNamespace

import pytest
from conftest import ROOT

from bench import devtrace, flops, harness, peaks
from repro.telemetry import TelemetrySession
from repro.telemetry.spans import Span

SHAPES = flops.SageShapes(batch=2000, fanouts=(10, 25), feature_dim=100, hidden=256, classes=47)
KIND = "TPU v5 lite"

#: One step of the staged path: (name, start, end, child seconds).
STEP = [
    ("step", 0.00, 1.00, 0.90),
    ("sample", 0.00, 0.10, 0.0),
    ("fetch.probe", 0.10, 0.12, 0.0),
    ("decision", 0.12, 0.15, 0.02),
    ("agent.infer", 0.13, 0.15, 0.0),
    ("fetch.commit", 0.15, 0.20, 0.0),
    ("train", 0.20, 0.90, 0.0),
]
DEPTH = {"step": 0}

#: Two steps' device activity (ns): ops overlap inside the first module.
TRACE = {
    "window": [0.0, 2e9],
    "devices": {
        "/device:TPU:0": {
            "ops": [["fusion", 3.0e8, 1.0e8], ["dot", 3.5e8, 1.0e8], ["fusion", 1.3e9, 1.0e8]],
            "modules": [["jit_sage_grads", 3.0e8, 1.5e8], ["jit_sage_grads", 1.3e9, 1.0e8]],
        }
    },
}


def recorded_session():
    s = TelemetrySession(label="test")
    for k in range(2):
        for name, t0, t1, child in STEP:
            sp = Span(s.tracer, name, -1, name.split(".")[0], 0)
            sp.t0, sp.t1, sp.child_s = t0 + k, t1 + k, child
            sp.depth = DEPTH.get(name, 1 if name != "agent.infer" else 2)
            s.tracer.spans.append(sp)
    return s


def logs():
    # Two trainers, two steps.
    lg = SimpleNamespace
    return [
        lg(unique_remote=[100, 100], comm_missed=[60, 40], comm_volume=[60, 70]),
        lg(unique_remote=[50, 50], comm_missed=[50, 30], comm_volume=[50, 30]),
    ]


@pytest.fixture
def run():
    return harness.Run(
        cell=None, device_kind=KIND, shapes=SHAPES, trainers=4, setup_s=12.5,
        window_s=2.0, steps=2, seeds=16000, result=SimpleNamespace(logs=logs()),
        session=recorded_session(), trace=TRACE,
    )


def read(name, run):
    return harness.load_reader(ROOT / "bench", name)(run)


def test_host_clock_metrics(run):
    assert read("seeds_per_s", run) == 8000.0
    assert read("setup_s", run) == 12.5


def test_span_metrics(run):
    assert read("sample_ms", run) == pytest.approx(100.0)
    # decision self 0.01 s + agent.infer 0.02 s per step.
    assert read("decide_ms", run) == pytest.approx(30.0)
    assert read("fetch_ms", run) == pytest.approx(70.0)
    assert read("train_ms", run) == pytest.approx(700.0)


def test_counter_metrics(run):
    # 300 remote, 180 missed -> 120 hits.
    assert read("buffer_hit_pct", run) == pytest.approx(40.0)
    # 210 rows x 100 floats x 4 B over 2 steps.
    assert read("remote_MB_per_step", run) == pytest.approx(210 * 400 / 1e6 / 2)


def test_trace_metrics(run):
    pk = peaks.peak(KIND)
    # Busy: [0.3, 0.45] and [1.3, 1.4] s -> 0.25 s of 2 s.
    assert read("device_idle_pct", run) == pytest.approx(87.5)
    least = max(flops.sage_grads_flops(SHAPES) / pk.flops_bf16,
                flops.sage_grads_bytes(SHAPES) / pk.hbm_bytes_per_s)
    assert read("sage_grads_roofline", run) == pytest.approx(100 * least * 2 / 0.25)
    mfu = 100 * flops.sage_grads_flops(SHAPES) * 4 * 2 / 2.0 / pk.flops_bf16
    assert read("sage_mfu", run) == pytest.approx(mfu)


def test_untraced_run_reads_nothing_from_spans_or_trace(run):
    run.session = None
    run.trace = None
    for name in ("sample_ms", "decide_ms", "fetch_ms", "train_ms", "sage_mfu",
                 "sage_grads_roofline", "device_idle_pct"):
        assert read(name, run) is None


def test_roofline_without_its_kernel_is_silent(run):
    run.trace = {"window": TRACE["window"], "devices": {"/device:TPU:0": {
        "ops": TRACE["devices"]["/device:TPU:0"]["ops"], "modules": []}}}
    assert read("sage_grads_roofline", run) is None


def test_breakdown_names_idle_time_by_host_span(run):
    out = harness._breakdown(run, anchor_perf_s=run.session.tracer.origin)
    ops = dict(out["device_ops"])
    assert ops["jit_sage_grads/fusion"] == pytest.approx(0.2)
    assert ops["jit_sage_grads/dot"] == pytest.approx(0.1)
    idle = dict(out["idle_gaps"])
    # Per step the device is busy inside "train" only (0.3-0.45 and
    # 1.3-1.4 s); the rest of the 2 s window is idle.
    assert sum(idle.values()) == pytest.approx(1.75)
    assert idle["sample"] == pytest.approx(0.2)
    assert idle["train"] == pytest.approx(1.4 - 0.25)
    assert all(math.isfinite(v) for v in idle.values())


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peak("Some Other Chip")


def test_busy_is_a_union():
    iv = devtrace.union(__import__("numpy").array([[0, 2], [1, 3], [5, 6]], float))
    assert iv.tolist() == [[0, 3], [5, 6]]
