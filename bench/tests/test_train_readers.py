"""The training step's readers (``feature_gather_ms``, ``upload_ms``,
``grads_ms``, ``update_ms``, ``h2d_MB_per_step``) on a hand-made
telemetry session, and the idle-time breakdown they make possible."""

from __future__ import annotations

from types import SimpleNamespace

import pytest
from conftest import ROOT

from bench import harness
from repro.telemetry import TelemetrySession
from repro.telemetry.spans import Span

#: One traced step with two trainers: (name, start, end, depth). The
#: ``train`` span's children take all but 0.01 s of it.
STEP = [
    ("step", 0.00, 1.00, 0),
    ("sample", 0.00, 0.10, 1),
    ("train", 0.10, 1.00, 1),
    ("train.gather", 0.10, 0.30, 2),
    ("train.upload", 0.30, 0.45, 2),
    ("train.grads", 0.45, 0.50, 2),
    ("train.gather", 0.50, 0.70, 2),
    ("train.upload", 0.70, 0.85, 2),
    ("train.grads", 0.85, 0.90, 2),
    ("train.update", 0.90, 0.99, 2),
]
SPAN_METRICS = ("feature_gather_ms", "upload_ms", "grads_ms", "update_ms")
#: Two trainers' uploads per step: 2 x 208.808 MB, over two steps.
H2D_BYTES = 2 * 2 * 208_808_000

#: Device work inside each ``train.grads`` span (ns), two steps.
TRACE = {
    "window": [0.0, 2e9],
    "devices": {"/device:TPU:0": {
        "ops": [["fusion", (k + t) * 1e9, 0.05e9] for k in (0, 1) for t in (0.45, 0.85)],
        "modules": [],
    }},
}


def session(steps=STEP, h2d=H2D_BYTES):
    s = TelemetrySession(label="test")
    for k in range(2):
        for name, t0, t1, depth in steps:
            sp = Span(s.tracer, name, -1, name.split(".")[0], 0)
            sp.t0, sp.t1, sp.depth = t0 + k, t1 + k, depth
            sp.child_s = sum(
                b - a for n, a, b, d in steps if d == depth + 1 and t0 <= a and b <= t1
            )
            s.tracer.spans.append(sp)
    if h2d is not None:
        s.registry.counter("device.h2d_bytes").add(h2d)
    return s


def make_run(sess, trace=None):
    return harness.Run(
        cell=None, device_kind="TPU v5 lite", shapes=None, trainers=2,
        setup_s=1.0, window_s=2.0, steps=2, seeds=8000, result=SimpleNamespace(logs=[]),
        session=sess, trace=trace,
    )


def read(name, run):
    return harness.load_reader(ROOT / "bench", name)(run)


@pytest.mark.parametrize(
    "name, per_step_ms",
    [("feature_gather_ms", 400.0), ("upload_ms", 300.0), ("grads_ms", 100.0),
     ("update_ms", 90.0)],
)
def test_span_readers(name, per_step_ms):
    assert read(name, make_run(session())) == pytest.approx(per_step_ms)


def test_parts_and_train_residue_partition_train():
    # train_ms is the inclusive time of ``train``: its four parts and the
    # loop's own residue (the span's self time) add up to it.
    run = make_run(session())
    parts = sum(read(n, run) for n in SPAN_METRICS)
    residue = 1e3 * run.span_self_s(names=("train",)) / run.steps
    assert residue == pytest.approx(10.0)
    assert parts + residue == pytest.approx(read("train_ms", run))
    assert read("train_ms", run) == pytest.approx(900.0)


def test_h2d_reader():
    assert read("h2d_MB_per_step", make_run(session())) == pytest.approx(417.616)


@pytest.mark.parametrize("name", SPAN_METRICS + ("h2d_MB_per_step",))
def test_untraced_run_reads_nothing(name):
    assert read(name, make_run(None)) is None


@pytest.mark.parametrize("name", SPAN_METRICS + ("h2d_MB_per_step",))
def test_program_without_the_parts_reads_nothing(name):
    # A program whose ``train`` span has no children and that counts no
    # upload (as before these spans existed): every reader is silent.
    only_train = [row for row in STEP if not row[0].startswith("train.")]
    assert read(name, make_run(session(only_train, h2d=None))) is None


def test_breakdown_names_idle_time_by_training_part():
    run = make_run(session(), TRACE)
    out = harness._breakdown(run, anchor_perf_s=run.session.tracer.origin)
    idle = dict(out["idle_gaps"])
    # Per step: gather 0.4 s, upload 0.3 s, grads 0.1 s less 0.1 s of
    # device work, update 0.09 s, train's residue 0.01 s, sample 0.1 s.
    assert idle["train.gather"] == pytest.approx(0.8)
    assert idle["train.upload"] == pytest.approx(0.6)
    assert idle["train.update"] == pytest.approx(0.18)
    assert idle["train"] == pytest.approx(0.02)
    # the device is busy all through train.grads (to float rounding of
    # the clock mapping)
    assert idle.get("train.grads", 0.0) == pytest.approx(0.0, abs=1e-9)
    assert sum(idle.values()) == pytest.approx(2.0 - 0.2)
