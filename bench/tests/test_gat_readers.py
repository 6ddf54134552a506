"""The readers this model's cells add (``gather_MB_per_step``,
``gat_mfu``, ``gat_grads_roofline``) on hand-made runs and traces, and
silent where the program or the trace has nothing for them."""

from __future__ import annotations

from types import SimpleNamespace

import pytest
from conftest import ROOT

from bench import harness, peaks
from bench.models import gat
from repro.telemetry import TelemetrySession

SHAPES = gat.Shapes(batch=512, fanouts=(10, 10, 10), feature_dim=100, hidden=128, heads=4,
                    classes=47)
KIND = "TPU v5 lite"
#: Ids per trainer step: 512 + 5,120 + 51,200 + 512,000.
IDS = 568_832

#: Two steps' device activity (ns): two ``gat_grads`` executions of
#: 40 ms and 60 ms, and one of another program.
TRACE = {
    "window": [0.0, 2e9],
    "devices": {"/device:TPU:0": {
        "ops": [["fusion", 3.0e8, 4.0e7], ["fusion", 1.3e9, 6.0e7]],
        "modules": [["jit_gat_grads", 3.0e8, 4.0e7], ["jit_gat_grads", 1.3e9, 6.0e7],
                    ["jit_other", 1.5e9, 1.0e7]],
    }},
}


def session(rows=None):
    s = TelemetrySession(label="test")
    if rows is not None:
        s.registry.counter("train.rows_gathered").add(rows)
    return s


def make_run(sess=None, trace=None, shapes=SHAPES):
    return harness.Run(
        cell=SimpleNamespace(model=gat), device_kind=KIND, shapes=shapes, trainers=4,
        setup_s=1.0, window_s=2.0, steps=2, seeds=4096, result=SimpleNamespace(logs=[]),
        session=sess, trace=trace,
    )


def read(name, run):
    return harness.load_reader(ROOT / "bench", name)(run)


@pytest.mark.parametrize(
    "ids, mb",
    # GAT: 4 trainers x 568,832 ids x 100 floats x 4 B = 910.1312 MB a
    # step; GraphSAGE at batch 2000, fanout (10, 25): 4 x 522,000 ids,
    # 835.2 MB.
    [(IDS, 910.1312), (2000 + 20_000 + 500_000, 835.2)],
)
def test_gather_reader(ids, mb):
    run = make_run(session(rows=2 * 4 * ids))
    assert read("gather_MB_per_step", run) == pytest.approx(mb)


def test_gat_mfu():
    pk = peaks.peak(KIND)
    # 4 trainers x 2 steps of the model's operations over a 2 s window.
    expect = 100 * gat.grads_flops(SHAPES) * 4 * 2 / 2.0 / pk.flops_bf16
    assert read("gat_mfu", make_run(trace=TRACE)) == pytest.approx(expect)
    assert 0 < expect < 100


def test_gat_grads_roofline():
    pk = peaks.peak(KIND)
    least = max(gat.grads_flops(SHAPES) / pk.flops_bf16,
                gat.grads_bytes(SHAPES) / pk.hbm_bytes_per_s)
    # Bound by bytes at the cell's shapes: 235.8 MB at 819 GB/s.
    assert least == gat.grads_bytes(SHAPES) / pk.hbm_bytes_per_s
    # Two executions in 0.1 s of device time; the other program is not
    # counted.
    assert read("gat_grads_roofline", make_run(trace=TRACE)) == pytest.approx(
        100 * least * 2 / 0.1
    )


@pytest.mark.parametrize("name", ["gather_MB_per_step", "gat_mfu", "gat_grads_roofline"])
def test_untraced_run_reads_nothing(name):
    assert read(name, make_run()) is None


def test_silent_where_there_is_nothing_to_read():
    # A program that counts no gathered rows (as before the counter), a
    # trace without the model's program.
    assert read("gather_MB_per_step", make_run(session())) is None
    no_program = {"window": TRACE["window"], "devices": {"/device:TPU:0": {
        "ops": TRACE["devices"]["/device:TPU:0"]["ops"], "modules": [["jit_other", 0, 1e7]]}}}
    assert read("gat_grads_roofline", make_run(trace=no_program)) is None
