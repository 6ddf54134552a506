"""The check that decides ``correct`` passes the program and fails its
control and every fault a cell can have, on a tiny cell on the CPU with
the products-sage cell's limits.

The control is the reference computed in bfloat16 put in the program's
place. The faults are planted in the program underneath a whole run:
a step that returns its state unchanged (from the first step, or only
in the window), half of each batch left out, the exchange of gradients
between trainers left out, the weights dropped between ``run()`` calls,
and an answer of the prefetch accounting altered where it is produced.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

import repro.gnn.sage as sage
import repro.runtime.stage as stage
from bench import check, harness, reference
from repro.gnn import DistributedTrainer

CELL = "tiny-sage.rudder-async"


def _run(root, graph_cache):
    return harness.run_cell(root, CELL, 11, 0.5, False, time.perf_counter(), graph_cache)


def test_sound_program_is_correct(tiny_root, graph_cache):
    out = _run(tiny_root, graph_cache)
    assert out["correct"] is True


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bfloat16_control_is_not_correct(tiny_root, graph_cache, seed):
    cell = harness.resolve_cell(tiny_root, harness.load_spec(tiny_root), CELL)
    job = harness.build(cell, seed, graph_cache)
    harness.warm_up(job)
    job.run()
    table = reference.device_table(job.parts.graph.features)
    _, window_at = harness.followed_steps(job)
    ref = harness.reference_run(job, table)
    losses, _, after = harness.reference_run(job, table, dtype=jnp.bfloat16)
    readings = check.training_readings(job.w0, losses, after, *ref, job.lr, window_at)
    readings.update(steps_unchanged=0, accounting_mismatches=0)
    ok, _ = check.verdict(readings, cell.config["limits"])
    assert not ok
    ok, _ = check.verdict(harness.readings(job), cell.config["limits"])
    assert ok


def _unchanged(orig):
    def grads(*a):
        loss, g = orig(*a)
        return loss, jax.tree_util.tree_map(jnp.zeros_like, g)
    return grads


def _half_batch(orig):
    def grads(params, x_seed, x_n1, x_n2, labels):
        h = len(labels) // 2
        return orig(params, x_seed[:h], x_n1[:h], x_n2[:h], labels[:h])
    return grads


def _no_exchange(orig, P=4):
    calls = [0]

    def grads(*a):
        loss, g = orig(*a)
        k = calls[0] % P
        calls[0] += 1
        return loss, jax.tree_util.tree_map(lambda x: x * (P if k == 0 else 0), g)
    return grads


def _unchanged_in_window(orig):
    def grads(*a):
        loss, g = orig(*a)
        if CALLS[0] > 1:
            g = jax.tree_util.tree_map(jnp.zeros_like, g)
        return loss, g
    return grads


CALLS = [0]


@pytest.mark.parametrize(
    "fault", [_unchanged, _unchanged_in_window, _half_batch, _no_exchange]
)
def test_training_fault_is_not_correct(tiny_root, graph_cache, monkeypatch, fault):
    inner = DistributedTrainer._run_impl

    def run_impl(self):
        CALLS[0] += 1
        return inner(self)

    CALLS[0] = 0
    monkeypatch.setattr(DistributedTrainer, "_run_impl", run_impl)
    monkeypatch.setattr(sage, "sage_grads", fault(sage.sage_grads))
    out = _run(tiny_root, graph_cache)
    assert out["correct"] is False
    assert out["checks"]["accounting_mismatches"]["value"] == 0


def test_state_dropped_between_calls_is_not_correct(tiny_root, graph_cache, monkeypatch):
    inner = DistributedTrainer._run_impl
    first = {}

    def run_impl(self):
        self.params = first.setdefault(id(self), self.params)
        return inner(self)

    monkeypatch.setattr(DistributedTrainer, "_run_impl", run_impl)
    out = _run(tiny_root, graph_cache)
    assert out["correct"] is False
    checks = out["checks"]
    assert checks["loss_gap"]["value"] <= checks["loss_gap"]["limit"]
    assert checks["window_update_gap"]["value"] > checks["window_update_gap"]["limit"]


def test_altered_fetch_set_is_not_correct(tiny_root, graph_cache, monkeypatch):
    inner = stage.SampleStage.run

    def run(self, *a):
        minibatches, remote, n_remote = inner(self, *a)
        remote[0] = remote[0][:-1]
        n_remote = n_remote.copy()
        n_remote[0] -= 1
        return minibatches, remote, n_remote

    monkeypatch.setattr(stage.SampleStage, "run", run)
    out = _run(tiny_root, graph_cache)
    assert out["correct"] is False
    assert out["checks"]["accounting_mismatches"]["value"] > 0
