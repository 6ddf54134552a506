"""Vectorized minibatch loop: the drop-in replacement for the legacy
per-trainer simulation in :meth:`repro.gnn.train.DistributedTrainer.run`.

Per minibatch the driver pushes the whole cluster through the explicit
three-stage pipeline of :mod:`repro.runtime.stage` (the legacy loop ran
the same dataflow inline, per PE, P times):

1. **sample** — :class:`SampleStage` advances all P trainers' fanout
   expansions in one batched pass over the shared CSR
   (:class:`repro.graph.sampler.SamplerPlane`: dense ``(P, B)`` seed
   blocks, ``(P, B, f1)`` / ``(P, B*f1, f2)`` neighbor blocks, fused
   sort/first-mask unique + remote extraction across all P frontiers);
2. **decide** — :class:`FetchStage.probe` answers every PE's buffer
   membership in one batched query, and the probe metrics feed the
   double-buffered :class:`DecisionStage` over the batched
   :class:`repro.core.controller.DecisionPlane` (heuristics as dense
   ``(P,)`` masks, adaptive controllers behind the batched inference
   pipe with per-PE async/sync latency accounting);
3. **fetch** — :class:`FetchStage.commit` closes the round: one batched
   scoring pass under the engine's policy, one batched replacement
   round, and the run's wall-clock time engine (:mod:`repro.sim` —
   closed-form §4.5.3 constants / per-pair
   :class:`repro.graph.generate.Topology` costs, or the discrete-event
   cluster simulator) — plus the (exact) GNN training step.

Every stage preserves the legacy loop's per-PE operation order, so
hit/miss/byte counts, decision streams and modeled step times are
bit-identical — asserted by ``tests/test_runtime_parity.py``.
See ``docs/ARCHITECTURE.md`` for the diagram.
"""

from __future__ import annotations

import warnings

import jax
import numpy as np

from .. import telemetry as tel
from ..core.controller import (
    FixedController,
    NoPrefetchController,
    PeriodicController,
)
from ..core.metrics import Metrics
from ..sim import StepComm
from .stage import DecisionStage, FetchStage, FusedFetchStage, SampleStage


def _train_step(trainer, minibatches) -> float:
    """One synchronous SGD step over every trainer's minibatch; returns
    the mean of their losses.

    Per trainer, in order: its features are gathered on the host
    (``train.gather``), uploaded (``train.upload``), and ``sage_grads``
    runs on them with the loss read back (``train.grads``). Then the
    gradients are summed in trainer order, averaged and applied
    (``train.update``); all of it inside one ``train`` span. While a
    session is active the upload and the update wait for their arrays,
    so each span holds its own transfer or device work; with telemetry
    off nothing waits, and the upload is the one ``sage_grads`` would
    make of the host arrays.
    """
    from ..gnn.sage import sage_grads

    P = trainer.parts.num_parts
    tree_map = jax.tree_util.tree_map
    loss_acc = 0.0
    grads = []
    with tel.span("train"):
        for mb in minibatches:
            with tel.span("train.gather") as sp:
                host = (*trainer._features_of(mb), mb.labels)
                sp.nbytes = sum(x.nbytes for x in host[:3])
            with tel.span("train.upload") as sp:
                batch = jax.device_put(host)
                if tel.enabled():
                    jax.block_until_ready(batch)
                    sp.nbytes = sum(x.nbytes for x in batch)
                    tel.count("device.h2d_bytes", sp.nbytes)
            with tel.span("train.grads"):
                loss, g = sage_grads(trainer.params, *batch)
                # The call holds its inputs until it has run; dropping them
                # here keeps one trainer's inputs on the device at a time,
                # as the implicit upload of host arrays did.
                del batch
                loss_acc += float(loss) / P
                tel.count("device.d2h_bytes", loss.nbytes)
            grads.append(g)
        with tel.span("train.update"):
            acc = grads[0]
            for g in grads[1:]:
                acc = tree_map(lambda a, b: a + b, acc, g)
            mean = tree_map(lambda g: g / P, acc)
            trainer.params = tree_map(
                lambda prm, g: prm - trainer.lr * g, trainer.params, mean
            )
            if tel.enabled():
                jax.block_until_ready(trainer.params)
    return loss_acc


def _final_accuracy(trainer) -> float:
    """Accuracy of the trained weights on one minibatch of the first
    (up to) 512 training nodes; 0.0 when the run trains no model."""
    if not trainer.train_model:
        return 0.0
    from ..gnn.sage import sage_accuracy

    batch = trainer.graph.train_nodes[: min(512, len(trainer.graph.train_nodes))]
    minibatch = trainer.sampler.sample(batch, trainer.rng)
    x_seed, x_n1, x_n2 = trainer._features_of(minibatch)
    return float(
        sage_accuracy(trainer.params, x_seed, x_n1, x_n2, minibatch.labels)
    )


def run_vectorized(trainer) -> "RunResult":  # noqa: F821 — see lazy import
    """Execute ``trainer``'s experiment on the vectorized runtime.

    ``trainer`` is a :class:`repro.gnn.train.DistributedTrainer`; its
    :class:`PrefetchEngine` (built in ``__init__`` alongside the legacy
    buffers, including any warm start) carries all per-PE buffer state.
    With ``DistributedTrainer(device=...)`` set, the per-step hot path
    runs device-resident instead (:func:`run_device`) — bit-identical
    streams, one fused kernel launch per step.
    """
    if getattr(trainer, "device", None):
        from ..kernels import ops

        # Tri-state device eligibility on the graph's *global* id
        # universe (id_base + local index): the narrow int32 megakernel
        # serves id_base == 0 graphs up to INT32_ID_MAX; bigger ids —
        # the int32 ceiling this used to fall back on — take the wide
        # (hi, lo) word-pair path up to WIDE_ID_MAX (~2^61). Only
        # beyond that does the run degrade to the staged pipeline
        # (identical streams, no device residency). Counted (not just
        # warned) so sweeps can report how many cells took the staged
        # path; the warning itself fires once per trainer, not per run.
        max_id = trainer.graph.id_base + trainer.graph.num_nodes - 1
        if ops.wide_id_eligible(max_id):
            return run_device(trainer)
        tel.count("device.fallback_int64")
        if not getattr(trainer, "_warned_int64_fallback", False):
            trainer._warned_int64_fallback = True
            warnings.warn(
                "device=... requested but graph node ids exceed int32 "
                "and the wide-id bound; falling back to the staged "
                "pipeline",
                RuntimeWarning,
                stacklevel=2,
            )
    # Deferred: repro.gnn.train imports the engine from this package.
    from ..gnn.train import RunResult, TrainerLog

    P = trainer.parts.num_parts
    sample = SampleStage(
        trainer.sampler_plane, P, trainer._seed_batch, trainer.parts.part_of
    )
    decide = DecisionStage(trainer.controllers)
    time_engine = trainer.make_time_engine()
    fetch = FetchStage(
        trainer.engine,
        decide.uses_buffer,
        decide.inference_cost,
        time_engine,
        trainer.graph.features.shape[1],
        trainer.mode,
        part_of=trainer.parts.part_of,
        store=trainer.feature_store,
        feature_bytes=trainer.tm.feature_bytes,
    )

    logs = [TrainerLog() for _ in range(P)]
    epoch_times: list[float] = []
    losses: list[float] = []
    recorder = trainer.make_trace_recorder()

    for epoch in range(trainer.epochs):
        epoch_time = 0.0
        for mb in range(trainer.mb_per_epoch):
            _step_sp = tel.begin("step", plane="runtime")
            # -- stage 1: batched sampling ----------------------------- #
            minibatches, remote, n_remote = sample.run(epoch, mb, trainer.rng)

            # -- stage 2: batched probe + controller decisions --------- #
            probe = fetch.probe(remote, n_remote)
            decide.submit(
                [
                    Metrics(
                        minibatch=mb,
                        total_minibatches=trainer.mb_per_epoch,
                        epoch=epoch,
                        total_epochs=trainer.epochs,
                        pct_hits=float(probe.pct_hits[p]),
                        comm_volume=int(probe.comm[p]),
                        replaced_pct=float(probe.replaced_pct[p]),
                        buffer_occupancy=float(probe.occupancy[p]),
                        buffer_capacity=int(trainer.engine.capacity[p]),
                    )
                    for p in range(P)
                ]
            )
            decisions, stalls = decide.collect()

            # -- stage 3: scoring + replacement + accounting ----------- #
            commit = fetch.commit(decisions, stalls)

            for p in range(P):
                logs[p].pct_hits.append(float(probe.pct_hits[p]))
                logs[p].comm_volume.append(int(commit.total_comm[p]))
                logs[p].comm_missed.append(int(probe.comm[p]))
                logs[p].occupancy.append(float(commit.occupancy[p]))
                logs[p].unique_remote.append(int(n_remote[p]))
                logs[p].replaced.append(int(commit.replaced[p]))
                logs[p].decisions.append(bool(decisions[p]))
                logs[p].step_time.append(float(commit.step_time[p]))
                if trainer.feature_store is not None:
                    logs[p].bytes_measured.append(int(commit.bytes_measured[p]))
                    logs[p].bytes_modeled.append(int(commit.bytes_modeled[p]))
                    logs[p].fetch_seconds.append(float(commit.fetch_seconds))
                    logs[p].feat_sums.append(float(commit.feat_sums[p]))
            epoch_time += float(commit.step_time.max())

            store_kwargs: dict = {}
            if trainer.feature_store is not None:
                store_kwargs = dict(
                    feat_sums=commit.feat_sums,
                    bytes_measured=commit.bytes_measured,
                    bytes_modeled=commit.bytes_modeled,
                    fetch_time_measured=np.full(
                        P, commit.fetch_seconds, dtype=np.float64
                    ),
                )
            if recorder is not None:
                recorder.record_step(
                    seeds=[m.seeds for m in minibatches],
                    remote=remote,
                    missed=commit.missed,
                    placed=commit.placed,
                    decisions=decisions,
                    stalls=stalls,
                    pct_hits=probe.pct_hits,
                    hits=probe.hits,
                    n_remote=n_remote,
                    replaced=commit.replaced,
                    total_comm=commit.total_comm,
                    occupancy_pre=probe.occupancy,
                    occupancy_post=commit.occupancy,
                    step_times=commit.step_time,
                    controllers=trainer.controllers,
                    **store_kwargs,
                )

            if trainer.train_model:
                losses.append(_train_step(trainer, minibatches))
            tel.end(_step_sp)
        epoch_times.append(epoch_time)

    accuracy = _final_accuracy(trainer)

    trace = None
    if recorder is not None:
        trace = recorder.finalize(epoch_times, time_engine.events)
        trainer.last_trace = trace

    return RunResult(
        variant=trainer.variant,
        epoch_times=epoch_times,
        losses=losses,
        accuracy=accuracy,
        logs=logs,
        controllers=trainer.controllers,
        graph_meta=trainer.graph_meta,
        sim_events=time_engine.events,
        trace=trace,
    )


def _device_raw_supported(trainer) -> bool:
    """True when every PE's seed block has the same constant length for
    all minibatches — the dense ``(P, Mt)`` frontier block the
    single-launch raw path uploads. A PE with ``0 < len(local_train) <
    batch_size`` yields ragged blocks (see ``_seed_batch``'s wraparound),
    which fall back to the PR 7 staged-gather device loop."""
    B = trainer.batch_size
    lens = set()
    for t in trainer.local_train:
        L = len(t)
        if L == 0:
            lens.add(min(B, len(trainer.graph.train_nodes)))
        elif L >= B:
            lens.add(B)
        else:
            return False
    return len(lens) == 1


def _check_cadence_eligible(trainer, time_engine, use_raw: bool) -> None:
    """``readback_every > 1`` trades per-step readbacks for epoch-level
    aggregates — valid only when nothing consumes the per-step id
    streams. Anything else is a config error, not a silent downgrade."""
    K = trainer.readback_every
    reasons = []
    if not use_raw:
        reasons.append("ragged per-PE seed blocks (staged fallback path)")
    if trainer.trace:
        reasons.append("trace recording needs per-step id streams")
    if trainer.feature_store is not None:
        reasons.append("the feature store moves per-step rows")
    if time_engine.needs_pairs:
        reasons.append("per-home comm pricing needs per-step id sets")
    bad = [
        type(c).__name__
        for c in trainer.controllers
        if type(c) not in (NoPrefetchController, FixedController, PeriodicController)
    ]
    if bad:
        reasons.append(
            f"controllers {sorted(set(bad))} read per-step metrics"
        )
    if reasons:
        raise ValueError(
            f"readback_every={K} is incompatible with this run: "
            + "; ".join(reasons)
        )


def _run_device_cadence(
    trainer, sample, decide, time_engine, dev, fused, K: int
) -> "RunResult":  # noqa: F821 — see lazy import
    """K-step readback cadence: the sweep-mode inner loop.

    Launches run exactly as in :func:`run_device`'s raw path, but each
    launch hands back only its ``(P, 4)`` ``[n_remote, hits, n_place,
    n_valid]`` counter block *as a device array*
    (``fused_step_raw(want="counts")``); every K launches one stacked
    ``device_get`` pulls them all. Per-step logs, stats and step times
    are then reconstructed from the counters — step t's probe counters
    ride in launch t, its replace counters in launch t+1 (the pipeline
    rotation), so a step is accounted once both launches have been
    flushed. :func:`_check_cadence_eligible` guarantees nothing in the
    run reads the per-step id streams this path never materializes; the
    counter-derived logs (hit/miss/replaced/occupancy counts, decision
    and step-time streams) are bit-identical to the K=1 path
    (``tests/test_fused_step.py``). ``last_*`` bookkeeping is stale in
    this mode — only :meth:`DeviceEngine.sync_to_engine`'s array state
    and the shared stats are written back.
    """
    from ..gnn.train import RunResult, TrainerLog

    jnp = dev._jnp
    P = dev.num_pes
    active = fused.active
    uses_buffer = fused.uses_buffer
    logs = [TrainerLog() for _ in range(P)]
    epoch_times = [0.0] * trainer.epochs
    losses: list[float] = []
    total = trainer.epochs * trainer.mb_per_epoch

    counters: list[np.ndarray] = []  # per launch, (P, 4) on host
    pending: list = []               # device counter blocks not yet pulled
    meta: list[tuple] = []           # per step: (epoch, decisions, stalls)
    done = 0                         # steps fully accounted

    def account(t: int) -> None:
        nonlocal epoch_times
        epoch, decisions, stalls = meta[t]
        probe_c, repl_c = counters[t], counters[t + 1]
        n_remote = probe_c[:, 0].astype(np.int64)
        hits = probe_c[:, 1].astype(np.int64)
        n_place = repl_c[:, 2].astype(np.int64)
        n_valid = repl_c[:, 3].astype(np.int64)
        do_rep = decisions & uses_buffer
        # Probe bookkeeping (lookup): inactive PEs probe nothing but
        # still fetch their whole remote set (hits == 0 there).
        lengths = np.where(active, n_remote, 0)
        miss = n_remote - hits
        dev.stats.lookups += lengths
        dev.stats.hits += hits
        dev.stats.misses += lengths - hits
        # Replacement bookkeeping (replace_round).
        rounds = do_rep & (n_place > 0)
        dev.stats.skipped_rounds += do_rep & (n_place == 0)
        dev.stats.replaced_total += np.where(rounds, n_place, 0)
        dev.stats.replacement_rounds += rounds
        replaced = np.where(rounds, n_place, 0)
        total_comm = miss + replaced
        step_time = time_engine.step(StepComm(miss, replaced), stalls)
        pct_hits = np.where(
            active,
            np.where(n_remote > 0, 100.0 * hits / np.maximum(n_remote, 1), 100.0),
            0.0,
        )
        occupancy = dev.occupancy_of(n_valid)
        for p in range(P):
            logs[p].pct_hits.append(float(pct_hits[p]))
            logs[p].comm_volume.append(int(total_comm[p]))
            logs[p].comm_missed.append(int(miss[p]))
            logs[p].occupancy.append(float(occupancy[p]))
            logs[p].unique_remote.append(int(n_remote[p]))
            logs[p].replaced.append(int(replaced[p]))
            logs[p].decisions.append(bool(decisions[p]))
            logs[p].step_time.append(float(step_time[p]))
        epoch_times[epoch] += float(step_time.max())

    def flush() -> None:
        nonlocal pending, done
        if pending:
            with tel.span("device.readback", plane="device"):
                block = jax.device_get(jnp.stack(pending))
            dev.transfers["d2h"] += 1
            dev.transfers["d2h_bytes"] += block.nbytes
            tel.count("device.d2h_bytes", block.nbytes)
            counters.extend(block)
            pending = []
        while done < len(meta) and done + 1 < len(counters):
            account(done)
            done += 1

    minibatches, touched = sample.run_raw(0, 0, trainer.rng)
    pending.append(
        dev.fused_step_raw(
            touched, fused._no_decision, fused._no_decision, active,
            want="counts",
        )
    )

    for step in range(total):
        _step_sp = tel.begin("step", plane="runtime")
        epoch, mb = divmod(step, trainer.mb_per_epoch)
        # The eligible controllers never read the metric values (that is
        # what _check_cadence_eligible enforces), so stale zeros keep
        # the decision stream bit-identical to the K=1 path while the
        # real counters sit on device awaiting the next flush.
        decide.submit(
            [
                Metrics(
                    minibatch=mb,
                    total_minibatches=trainer.mb_per_epoch,
                    epoch=epoch,
                    total_epochs=trainer.epochs,
                    pct_hits=0.0,
                    comm_volume=0,
                    replaced_pct=0.0,
                    buffer_occupancy=0.0,
                    buffer_capacity=int(trainer.engine.capacity[p]),
                )
                for p in range(P)
            ]
        )
        decisions, stalls = decide.collect()

        if step + 1 < total:
            e2, m2 = divmod(step + 1, trainer.mb_per_epoch)
            nxt_mb, nxt_touched = sample.run_raw(e2, m2, trainer.rng)
        else:
            nxt_mb = None
            nxt_touched = np.full((P, 0), -1, dtype=np.int64)
        pending.append(
            dev.fused_step_raw(
                nxt_touched, uses_buffer, decisions & uses_buffer, active,
                want="counts",
            )
        )
        meta.append((epoch, decisions, stalls))
        if len(pending) >= K:
            flush()

        if trainer.train_model:
            losses.append(_train_step(trainer, minibatches))

        minibatches = nxt_mb
        tel.end(_step_sp)

    flush()

    accuracy = _final_accuracy(trainer)

    dev.sync_to_engine()
    return RunResult(
        variant=trainer.variant,
        epoch_times=epoch_times,
        losses=losses,
        accuracy=accuracy,
        logs=logs,
        controllers=trainer.controllers,
        graph_meta=trainer.graph_meta,
        sim_events=time_engine.events,
        trace=None,
    )


def run_device(trainer) -> "RunResult":  # noqa: F821 — see lazy import
    """Device-resident twin of :func:`run_vectorized`.

    Buffer state lives in persistent jax arrays
    (:class:`repro.runtime.engine.DeviceEngine`) and each step issues
    exactly one fused score→replace→probe launch through
    :class:`repro.runtime.stage.FusedFetchStage`, pipeline-rotated so
    the host decision plane runs between probes::

        sample(0) ── prime launch [probe(0)]
        step t:   decide(t) → begin miss gather(t) → sample(t+1)
                  → launch [score(t), replace(t), probe(t+1)]
                  → accounting / trace / train for step t

    The interleaving of RNG draws (sample) and controller calls
    (decide) is identical to the staged loop, the in-kernel round order
    is identical to ``end_round`` → ``replace_round`` → ``lookup``, and
    the store's miss gather is dispatched *before* the next sample draw
    (the double-buffer overlap) — so every exact stream
    (hit/miss/byte/decision/feat_sums) is bit-identical to
    :func:`run_vectorized` and the committed golden traces
    (``tests/test_fused_step.py``). At the end of the run the device
    state is written back to ``trainer.engine`` for introspection.

    **Single-launch raw path.** When every PE's seed block has one
    constant length (:func:`_device_raw_supported` — the common case),
    the loop skips the host dedup entirely: ``sample`` hands the raw
    ``(P, Mt)`` frontier to :meth:`FusedFetchStage.step_raw`, whose one
    launch also covers dedup and the feature gather, with one upload and
    one packed readback per step (``DeviceEngine.transfers`` audits
    this). Ragged seed blocks keep the PR 7 staged-gather loop. With
    ``DistributedTrainer(readback_every=K>1)``, sweep runs additionally
    batch the readbacks of K steps into one counter pull
    (:func:`_run_device_cadence`; per-step id streams are not
    materialized — gated by :func:`_check_cadence_eligible`).
    """
    from ..gnn.train import RunResult, TrainerLog
    from .engine import DeviceEngine

    P = trainer.parts.num_parts
    sample = SampleStage(
        trainer.sampler_plane, P, trainer._seed_batch, trainer.parts.part_of
    )
    decide = DecisionStage(trainer.controllers)
    time_engine = trainer.make_time_engine()
    backend = "jnp" if trainer.device is True else trainer.device
    dev = DeviceEngine(
        trainer.engine, backend=backend, part_of=trainer.parts.part_of
    )
    if trainer.feature_store is not None:
        dev.attach_store(trainer.feature_store)
    fused = FusedFetchStage(
        dev,
        decide.uses_buffer,
        decide.inference_cost,
        time_engine,
        trainer.graph.features.shape[1],
        trainer.mode,
        part_of=trainer.parts.part_of,
        store=trainer.feature_store,
        feature_bytes=trainer.tm.feature_bytes,
    )
    use_raw = _device_raw_supported(trainer)
    cadence = int(getattr(trainer, "readback_every", 1))
    if cadence > 1:
        _check_cadence_eligible(trainer, time_engine, use_raw)
        return _run_device_cadence(
            trainer, sample, decide, time_engine, dev, fused, cadence
        )

    logs = [TrainerLog() for _ in range(P)]
    epoch_times = [0.0] * trainer.epochs
    losses: list[float] = []
    recorder = trainer.make_trace_recorder()
    total = trainer.epochs * trainer.mb_per_epoch

    if use_raw:
        minibatches, touched = sample.run_raw(0, 0, trainer.rng)
        probe = fused.prime_raw(touched)
        remote, n_remote = probe.remote, probe.n_remote
    else:
        minibatches, remote, n_remote = sample.run(0, 0, trainer.rng)
        probe = fused.prime(remote, n_remote)

    for step in range(total):
        _step_sp = tel.begin("step", plane="runtime")
        epoch, mb = divmod(step, trainer.mb_per_epoch)
        decide.submit(
            [
                Metrics(
                    minibatch=mb,
                    total_minibatches=trainer.mb_per_epoch,
                    epoch=epoch,
                    total_epochs=trainer.epochs,
                    pct_hits=float(probe.pct_hits[p]),
                    comm_volume=int(probe.comm[p]),
                    replaced_pct=float(probe.replaced_pct[p]),
                    buffer_occupancy=float(probe.occupancy[p]),
                    buffer_capacity=int(trainer.engine.capacity[p]),
                )
                for p in range(P)
            ]
        )
        decisions, stalls = decide.collect()

        # Double buffer: this step's miss gather overlaps the next draw.
        fused.begin_gather()
        nxt_mb = None
        if step + 1 < total:
            e2, m2 = divmod(step + 1, trainer.mb_per_epoch)
            if use_raw:
                nxt_mb, nxt_touched = sample.run_raw(e2, m2, trainer.rng)
            else:
                nxt_mb, nxt_remote, nxt_n_remote = sample.run(
                    e2, m2, trainer.rng
                )
        elif use_raw:
            nxt_touched = np.full((P, 0), -1, dtype=np.int64)
        else:
            nxt_remote = [np.array([], dtype=np.int64) for _ in range(P)]
            nxt_n_remote = np.zeros(P, dtype=np.int64)

        if use_raw:
            commit, next_probe = fused.step_raw(decisions, stalls, nxt_touched)
        else:
            commit, next_probe = fused.step(
                decisions, stalls, nxt_remote, nxt_n_remote
            )

        for p in range(P):
            logs[p].pct_hits.append(float(probe.pct_hits[p]))
            logs[p].comm_volume.append(int(commit.total_comm[p]))
            logs[p].comm_missed.append(int(probe.comm[p]))
            logs[p].occupancy.append(float(commit.occupancy[p]))
            logs[p].unique_remote.append(int(n_remote[p]))
            logs[p].replaced.append(int(commit.replaced[p]))
            logs[p].decisions.append(bool(decisions[p]))
            logs[p].step_time.append(float(commit.step_time[p]))
            if trainer.feature_store is not None:
                logs[p].bytes_measured.append(int(commit.bytes_measured[p]))
                logs[p].bytes_modeled.append(int(commit.bytes_modeled[p]))
                logs[p].fetch_seconds.append(float(commit.fetch_seconds))
                logs[p].feat_sums.append(float(commit.feat_sums[p]))
        epoch_times[epoch] += float(commit.step_time.max())

        store_kwargs: dict = {}
        if trainer.feature_store is not None:
            store_kwargs = dict(
                feat_sums=commit.feat_sums,
                bytes_measured=commit.bytes_measured,
                bytes_modeled=commit.bytes_modeled,
                fetch_time_measured=np.full(
                    P, commit.fetch_seconds, dtype=np.float64
                ),
            )
        if recorder is not None:
            recorder.record_step(
                seeds=[m.seeds for m in minibatches],
                remote=remote,
                missed=commit.missed,
                placed=commit.placed,
                decisions=decisions,
                stalls=stalls,
                pct_hits=probe.pct_hits,
                hits=probe.hits,
                n_remote=n_remote,
                replaced=commit.replaced,
                total_comm=commit.total_comm,
                occupancy_pre=probe.occupancy,
                occupancy_post=commit.occupancy,
                step_times=commit.step_time,
                controllers=trainer.controllers,
                **store_kwargs,
            )

        if trainer.train_model:
            losses.append(_train_step(trainer, minibatches))

        minibatches = nxt_mb
        probe = next_probe
        if use_raw:
            remote, n_remote = probe.remote, probe.n_remote
        else:
            remote, n_remote = nxt_remote, nxt_n_remote
        tel.end(_step_sp)

    accuracy = _final_accuracy(trainer)

    dev.sync_to_engine()
    trace = None
    if recorder is not None:
        trace = recorder.finalize(epoch_times, time_engine.events)
        trainer.last_trace = trace

    return RunResult(
        variant=trainer.variant,
        epoch_times=epoch_times,
        losses=losses,
        accuracy=accuracy,
        logs=logs,
        controllers=trainer.controllers,
        graph_meta=trainer.graph_meta,
        sim_events=time_engine.events,
        trace=trace,
    )
