"""The comparison that decides a run's ``correct``.

Training (every cell): the program, driven through
``DistributedTrainer.run()`` from the benchmark's weights, against
``reference.train`` from the same weights on the same minibatches,
along every step of the warm-up and the window's first three:

* ``loss_gap``: the largest relative gap of a step's loss over the
  warm-up's first three steps;
* ``grad_gap``: the first step's mean gradient as the optimizer applied
  it, ``(w0 - w1) / lr``, by the worst leaf: the gap between the
  program's and the reference's norm of the leaf, over the reference's
  norm of that leaf or of the median leaf, whichever is larger;
* ``update_gap``: the same for the weights' change over the warm-up's
  first three steps;
* ``window_loss_gap``: the largest relative loss gap over the window's
  first three steps, the reference having followed the whole warm-up;
* ``window_update_gap``: the worst-leaf gap, as above, of the weights'
  change from ``w0`` to the end of the window's third step;
* ``steps_unchanged``: steps of the warm-up and the window after which
  the program's weights are bit for bit those before it. Limit 0.

Leaves whose reference gradient is under a thousandth of the median
leaf's move by round-off alone and are left out of the leaf gaps.

Prefetch accounting (every cell): ``accounting_mismatches`` counts the
(step, trainer) pairs of every step of the run, warm-up and window,
whose remote, miss or admission count differs from
``reference.Accounting``. Its limit is 0.
"""

from __future__ import annotations

import math

import numpy as np

#: A leaf whose reference gradient norm is under this share of the
#: median leaf's is left out of the gradient and update comparisons.
ROUNDOFF_LEAF = 1e-3


def _norms(tree: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64))) for k, v in tree.items()}


def worst_leaf_gap(observed: dict, reference: dict, keep) -> float:
    n_obs, n_ref = _norms(observed), _norms(reference)
    median = float(np.median([n_ref[k] for k in keep]))
    return max(abs(n_obs[k] - n_ref[k]) / max(n_ref[k], median) for k in keep)


def compared_leaves(ref_grads1: dict) -> list[str]:
    norms = _norms(ref_grads1)
    median = float(np.median(list(norms.values())))
    return [k for k, n in norms.items() if n >= ROUNDOFF_LEAF * median]


def training_readings(
    w0: dict,
    losses: list[float],
    after: list[dict],
    ref_losses: list[float],
    ref_grads1: dict,
    ref_after: list[dict],
    lr: float,
    window_at: int,
    first: int = 3,
) -> dict:
    """Readings along the followed steps. ``after[t]`` holds the weights
    after step t+1, as float64 leaves keyed like ``w0``; the window
    starts at step ``window_at``; the reference follows ``len(ref_losses)``
    steps, and the first ``first`` of the warm-up and of the window are
    compared."""
    keep = compared_leaves(ref_grads1)
    w0 = {k: np.asarray(v, np.float64) for k, v in w0.items()}

    def change(w):
        return {k: w[k] - w0[k] for k in w0}

    def loss_gap(lo, hi):
        return max(abs(a - b) / abs(b) for a, b in zip(losses[lo:hi], ref_losses[lo:hi]))

    n, end = min(first, window_at), len(ref_losses)
    if len(losses) < end or len(after) < end:
        return dict.fromkeys(
            ("loss_gap", "grad_gap", "update_gap", "window_loss_gap", "window_update_gap"),
            math.inf,
        )
    grads1 = {k: (w0[k] - after[0][k]) / lr for k in w0}
    return {
        "loss_gap": loss_gap(0, n),
        "grad_gap": worst_leaf_gap(grads1, ref_grads1, keep),
        "update_gap": worst_leaf_gap(change(after[n - 1]), change(ref_after[n - 1]), keep),
        "window_loss_gap": loss_gap(window_at, end),
        "window_update_gap": worst_leaf_gap(
            change(after[end - 1]), change(ref_after[end - 1]), keep
        ),
    }


def accounting_mismatches(logs, ref: dict) -> int:
    """(step, trainer) pairs whose counts differ from the reference.
    ``logs[p]`` is the program's per-trainer log over the same steps."""
    prog = {
        "remote": np.array([lg.unique_remote for lg in logs]).T,
        "missed": np.array([lg.comm_missed for lg in logs]).T,
        "replaced": np.array([lg.replaced for lg in logs]).T,
        "total": np.array([lg.comm_volume for lg in logs]).T,
    }
    expect = dict(ref, total=ref["missed"] + ref["replaced"])
    if any(prog[k].shape != expect[k].shape for k in prog):
        return int(max(v.size for v in expect.values()))
    bad = np.zeros(expect["remote"].shape, bool)
    for k in prog:
        bad |= prog[k] != expect[k]
    return int(bad.sum())


def verdict(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and, per number compared, its value beside its limit.
    A number that is not finite, or has no limit, fails."""
    checks = {}
    ok = True
    for name, value in readings.items():
        limit = limits.get(name)
        passed = limit is not None and math.isfinite(value) and value <= limit
        ok &= passed
        checks[name] = {"value": value, "limit": limit}
    return ok, checks
