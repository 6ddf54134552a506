#!/usr/bin/env python3
"""Smoke test of the trainer on a TPU, through its normal entry points.

Run from the root of a checkout:

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: phase B on a sharded store

Phase A trains GraphSAGE at full width — ``products`` at scale 10
(240,000 nodes, F=100, 47 classes), 4 partitions, batch 2000, fanout
(10, 25), hidden 256 — with the Rudder controller on the default
(staged) fetch path, for one epoch. Every loss must be finite, and the
first step's loss is recomputed on the host CPU backend and must agree
within a relative 1e-3 (the TPU's default f32 matmul precision).

Phase B runs the device-resident fused Pallas path
(``device="pallas"``) at the largest size whose fused launch compiles
for v5e, and requires its hit, miss, byte and decision streams to be
bit-identical to a staged run of the same configuration, with every
fused launch compiled by Mosaic (none interpreted, none on the jnp
oracle).

``--chips 4`` runs only phase B, with the feature store sharded over
the four chips, against the same run on a host (numpy) store: the
``feat_sums`` and byte streams must be bit-identical.

The script runs in one process that owns the chip and starts no other.
Printed seconds are smoke timings, not benchmark numbers. Without a TPU
it exits non-zero; it never carries on on the CPU. The last line of
standard output is the JSON result.
"""

from __future__ import annotations

import argparse
import copy
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

P = 4
WIDTHS = dict(fanouts=(10, 25), hidden_dim=256)
DECIDERS = ["gemma3-4b"] * P

#: Phase A: the paper's training job at full width on ``products``.
PHASE_A = dict(scale=10.0, batch_size=2000)
#: The TPU's default f32 matmuls take bf16 passes, so its loss differs
#: from the CPU's in the fourth digit (1.3e-4 relative on a v5e for this
#: seed); the run is deterministic, so 1e-3 leaves a wide margin.
LOSS_RTOL = 1e-3

#: Phase B: the fused launch builds dense (Mt, C), (K, C) and (K, K)
#: tiles, Mt = batch * (1 + 10 + 250), K = 2C (docs/KERNELS.md#fused_step).
#: Mosaic unrolls them into vector ops, so its compile time grows with
#: their area; this is the largest size whose launch compiles for v5e
#: in well under a minute (C = 1294, Mt = 8352).
PHASE_B = dict(scale=1.0, batch_size=32, epochs=1)
PHASE_B_WHY = (
    "largest fused launch that compiles for v5e in under a minute; "
    "larger ones compile, slower with the dense tiles' area"
)

STREAMS = (
    "pct_hits",
    "comm_volume",
    "comm_missed",
    "occupancy",
    "unique_remote",
    "replaced",
    "decisions",
    "step_time",
)
STORE_STREAMS = ("bytes_measured", "bytes_modeled", "feat_sums")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {what}")


def log(msg: str) -> None:
    print(msg, flush=True)


def partitions(scale: float):
    from repro.graph import generate, partition_graph

    return partition_graph(generate("products", seed=0, scale=scale), P)


def trainer(parts, **kw):
    from repro.gnn import DistributedTrainer

    return DistributedTrainer(
        parts,
        variant="rudder",
        deciders=DECIDERS,
        train_model=True,
        seed=0,
        **WIDTHS,
        **kw,
    )


def same_streams(a, b, fields) -> None:
    for p, (la, lb) in enumerate(zip(a.logs, b.logs)):
        for name in fields:
            check(
                getattr(la, name) == getattr(lb, name),
                f"stream {name} of PE {p} differs from the reference run",
            )


def phase_a() -> None:
    import jax
    import numpy as np

    from repro.gnn.sage import sage_loss
    from repro.gnn.train import index_blocks
    from repro.runtime.stage import SampleStage

    t0 = time.perf_counter()
    parts = partitions(PHASE_A["scale"])
    tr = trainer(parts, batch_size=PHASE_A["batch_size"], epochs=1)
    params0 = tr.params
    rng0 = copy.deepcopy(tr.rng)
    t1 = time.perf_counter()
    res = tr.run()
    t2 = time.perf_counter()
    losses = res.losses
    log(
        f"phase A: products scale={PHASE_A['scale']} nodes={parts.graph.num_nodes} "
        f"F={parts.graph.features.shape[1]} classes={parts.graph.num_classes} "
        f"P={P} batch={PHASE_A['batch_size']} fanouts={WIDTHS['fanouts']} "
        f"hidden={WIDTHS['hidden_dim']} C={tr.engine.max_capacity} "
        f"steps={len(losses)}"
    )
    log(f"phase A: losses={losses}")
    check(len(losses) == tr.mb_per_epoch, "one loss per training step")
    check(all(math.isfinite(x) for x in losses), "every loss is finite")

    # The first step again, on the host CPU backend: same minibatches
    # (the sampler's RNG from before the run), same initial weights.
    cpu = jax.devices("cpu")[0]
    minibatches, _, _ = SampleStage(
        tr.sampler_plane, P, tr._seed_batch, parts.part_of
    ).run(0, 0, rng0)
    loss_cpu = 0.0
    with jax.default_device(cpu):
        params_cpu = jax.device_put(params0, cpu)
        table_cpu = jax.device_put(parts.graph.features, cpu)
        loss_fn = jax.jit(sage_loss)
        for mb in minibatches:
            *ids, labels = jax.device_put(index_blocks(mb), cpu)
            rows = tr.feature_rows(table_cpu, None, ids)
            loss_cpu += float(loss_fn(params_cpu, *rows, labels)) / P
    rel = abs(losses[0] - loss_cpu) / abs(loss_cpu)
    log(
        f"phase A: first-step loss chip={losses[0]!r} cpu={loss_cpu!r} "
        f"rel_diff={rel!r}"
    )
    check(bool(np.isfinite(rel)) and rel <= LOSS_RTOL, "first loss matches the CPU")
    log(
        f"phase A smoke timings (not benchmark numbers): setup_s={t1 - t0:.3f} "
        f"run_s={t2 - t1:.3f} check_s={time.perf_counter() - t2:.3f}"
    )


def launch_counts(tr) -> dict[str, float]:
    reg = tr.last_telemetry.registry
    return {
        path: reg[f"device.launch.{path}"].total
        if f"device.launch.{path}" in reg
        else 0.0
        for path in ("compiled", "interpreted", "oracle")
    }


def phase_b(parts, *, store: str | None) -> None:
    """Fused Pallas path vs the reference run. With ``store`` unset the
    reference is the staged fetch path; with ``store="sharded"`` both
    runs are fused and carry a feature store, the reference's on the
    host (numpy) and the checked run's sharded over every chip."""
    from repro.store import FeatureStore

    kw = dict(batch_size=PHASE_B["batch_size"], epochs=PHASE_B["epochs"])
    fields = STREAMS
    if store is None:
        ref_tr = trainer(parts, **kw)
        tr = trainer(parts, device="pallas", telemetry=True, **kw)
    else:
        fields = STREAMS + STORE_STREAMS
        host = FeatureStore.for_partitions(parts, backend="numpy")
        sharded = FeatureStore.for_partitions(parts, backend="auto")
        check(sharded.backend == "jax", "auto store picks the sharded table")
        ref_tr = trainer(parts, device="pallas", feature_store=host, **kw)
        tr = trainer(
            parts, device="pallas", feature_store=sharded, telemetry=True, **kw
        )
    t0 = time.perf_counter()
    ref = ref_tr.run()
    t1 = time.perf_counter()
    res = tr.run()
    t2 = time.perf_counter()
    steps = len(res.logs[0].decisions)
    log(
        f"phase B: products scale={PHASE_B['scale']} nodes={parts.graph.num_nodes} "
        f"P={P} batch={PHASE_B['batch_size']} C={tr.engine.max_capacity} "
        f"Mt={PHASE_B['batch_size'] * (1 + 10 + 10 * 25)} steps={steps} "
        f"store={store or 'none'} ({PHASE_B_WHY})"
    )
    log(f"phase B: losses={res.losses}")
    check(all(math.isfinite(x) for x in res.losses), "every loss is finite")
    same_streams(res, ref, fields)
    counts = launch_counts(tr)
    log(f"phase B: fused launches {counts}")
    check(
        counts["compiled"] == steps + 1
        and counts["interpreted"] == 0
        and counts["oracle"] == 0,
        "every fused launch ran the compiled kernel",
    )
    log(
        f"phase B smoke timings (not benchmark numbers): reference_run_s="
        f"{t1 - t0:.3f} fused_run_s={t2 - t1:.3f}"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips",
        type=int,
        choices=(1, 4),
        default=1,
        help="4: run only phase B with the feature store sharded over 4 chips",
    )
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: run from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    # A compile cache that later runs from this checkout find again; an
    # explicit JAX_COMPILATION_CACHE_DIR is JAX's own to honour.
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)}",
            file=sys.stderr,
        )
        return 1
    log(f"device: {dev.device_kind} x{len(devices)}")

    from repro.kernels import ops

    check(not ops.interpret_mode(), "kernels compile (no interpret mode) on TPU")
    t0 = time.perf_counter()
    if args.chips == 1:
        phase_a()
        phase_b(partitions(PHASE_B["scale"]), store=None)
    else:
        phase_b(partitions(PHASE_B["scale"]), store="sharded")
    log(f"total smoke seconds (not a benchmark number): {time.perf_counter() - t0:.3f}")
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(devices),
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
