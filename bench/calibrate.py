"""Readings that the correctness limits are set from, on the chip.

From the root of a checkout, on the machine with the chip:

    python3 bench/calibrate.py --workload products-sage.rudder \\
        --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --control-seeds 1 2 3

For every seed it builds the cell's trainer, drives the warm-up and a
one-epoch window through ``run()`` as a benchmark run does, and prints
one JSON line of the program's readings against the reference
(``check.py``). For each control seed it also reads, on the same
minibatches, with the reference put in the program's place:

* ``control``: the reference computed in bfloat16 (the configuration
  states float32);
* ``half_batch``: each trainer's loss over half of its seeds;
* ``no_exchange``: trainer 0's gradient in place of the mean over
  trainers;
* ``restart``: the window's steps taken from ``w0`` again, as a program
  that drops its state between ``run()`` calls would.

A step that leaves its state unchanged reads 1 on ``grad_gap`` and
``update_gap`` by their definition and is counted exactly by
``steps_unchanged``; it needs no run. All readings run in one process,
so the graph is made once.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax
    import jax.numpy as jnp

    from bench import check, harness, reference

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 1
    cell = harness.resolve_cell(ROOT, harness.load_spec(ROOT), args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        job = harness.build(cell, seed)
        harness.warm_up(job)
        job.trainer.epochs = 1
        job.run()
        row = {"seed": seed, "kind": "program", **harness.readings(job)}
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        if seed not in args.control_seeds:
            continue
        table = reference.device_table(job.parts.graph.features)
        steps, window_at = harness.followed_steps(job)
        ref = harness.reference_run(job, table)

        def restart(**_):
            warm = reference.train(job.w0, table, steps[:window_at], job.lr)
            win = reference.train(job.w0, table, steps[window_at:], job.lr)
            return warm[0] + win[0], warm[1], warm[2] + win[2]

        for kind, run in (
            ("control", lambda: harness.reference_run(job, table, dtype=jnp.bfloat16)),
            ("half_batch", lambda: harness.reference_run(job, table, fault="half_batch")),
            ("no_exchange", lambda: harness.reference_run(job, table, fault="no_exchange")),
            ("restart", restart),
        ):
            losses, _, after = run()
            r = check.training_readings(job.w0, losses, after, *ref, job.lr, window_at)
            print(json.dumps({"seed": seed, "kind": kind, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
