"""Distributed GNN training driver — the paper's evaluation harness.

Runs the three variants of §5 on a partitioned graph:

* ``distdgl``      — no prefetch: every sampled remote node is fetched;
* ``fixed``        — static prefetch: replacement round every minibatch;
* ``massivegnn``   — warm-started buffer, fixed replacement interval;
* ``rudder``       — adaptive replacement via LLM agent / ML classifier
                     behind the async/sync queue protocol.

What is *exact*: partitioning, sampling, buffer membership/scoring,
hit/miss sets, remote fetch counts (bytes), decision streams, GNN
training math (JAX GraphSAGE or GAT, ``model=...``, with data-parallel
gradient averaging —
Rudder never alters sampling or training, so accuracy is unaffected by
the variant, as the paper states).

What is *modeled*: wall-clock epoch time, via the paper's own §4.5.3
performance model driven by the exact byte counts:

    async step time = max(T_DDP, T_COMM)          (inference hidden)
    sync  step time = T_DDP + T_COMM + T_A/C      (inference exposed)

with T_COMM = alpha + fetched_bytes / link_bw per trainer and the step
synchronised across trainers by the gradient all-reduce (max over PEs).
Constants are documented in :class:`TimeModel`. With ``topology=...``
the flat constants are replaced by the per-pair cluster cost model of
:class:`repro.graph.generate.Topology` (fetch RPCs priced by home
partition); the exact byte counts are unchanged.

With ``time_engine="event"`` the same exact streams are priced by the
discrete-event cluster simulator of :mod:`repro.sim` instead: per-trainer
and per-link timelines with max–min fair home-egress contention
(``congestion=...``), per-PE straggler/jitter compute multipliers
(``stragglers=...``), a wall-clock agent-daemon lane and
prefetcher-thread replacement overlap (``sim=SimConfig(...)``). With no
scenario injected the event engine reproduces the closed form
bit-identically (the parity contract of ``tests/test_runtime_parity.py``).

Two interchangeable execution paths produce the run (see
``docs/ARCHITECTURE.md``):

* ``runtime="vectorized"`` (default) — the batched multi-PE
  :class:`repro.runtime.PrefetchEngine` loop, used by every benchmark
  and the ``--sweep`` grid runner;
* ``runtime="legacy"`` — the original one-PE-at-a-time Python loop,
  kept as the semantic reference; ``tests/test_runtime_parity.py``
  asserts the two are bit-identical on hits, misses, bytes and decision
  streams for all four variants.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, NamedTuple

import jax
import numpy as np

from .. import telemetry as tel
from ..core import scoring
from ..core.buffer import PersistentBuffer
from ..core.controller import Controller, make_controller
from ..core.metrics import GraphMeta, Metrics
from ..graph.generate import (
    CongestionModel,
    Graph,
    StragglerModel,
    Topology,
    make_congestion,
    make_stragglers,
    make_topology,
)
from ..graph.partition import Partitioned
from ..graph.sampler import MiniBatch, NeighborSampler, SamplerPlane, unique_remote
from ..runtime.engine import PrefetchEngine
from . import gat, sage
from .sage import Rows

#: Lane width of a TPU vector register: a float32 table whose rows fill
#: whole lanes is laid out row-major, so a row gather needs no relayout.
LANES = 128


class Model(NamedTuple):
    """A model the trainer trains: ``init(key, feature_dim, hidden_dim,
    num_classes, heads, num_layers)`` makes its weights; ``grads`` and
    ``accuracy`` take ``(params, blocks, labels)``, ``blocks`` the seed
    block and one block per hop (:func:`index_blocks`), as features or
    :class:`Rows`."""

    init: Callable
    grads: Callable
    accuracy: Callable


# Each function is looked up on its module at the call, so that one
# replaced there (as the benchmark's fault tests replace sage_grads)
# is the one a trainer runs.
MODELS = {
    "sage": Model(
        init=lambda key, f, h, c, heads, layers: sage.init_sage(key, f, h, c),
        grads=lambda params, blocks, y: sage.sage_grads(params, *blocks, y),
        accuracy=lambda params, blocks, y: sage.sage_accuracy(params, *blocks, y),
    ),
    "gat": Model(
        init=lambda *a: gat.init_gat(*a),
        grads=lambda *a: gat.gat_grads(*a),
        accuracy=lambda *a: gat.gat_accuracy(*a),
    ),
}


def index_blocks(minibatch: MiniBatch) -> tuple[np.ndarray, ...]:
    """What a training step uploads of a minibatch: its seed ids and
    each hop's as int32 blocks of shapes ``(B,)``, ``(B, f1)``, ...,
    ``(B, f1, ..., fL)`` (local rows of the graph), and its labels."""
    blocks = [minibatch.seeds.astype(np.int32)]
    shape = blocks[0].shape
    for nbrs in minibatch.layer_nbrs:
        shape += nbrs.shape[-1:]
        blocks.append(nbrs.reshape(shape).astype(np.int32))
    return (*blocks, minibatch.labels.astype(np.int32))


@dataclass
class TimeModel:
    """Calibrated constants for the §4.5.3 performance model.

    ``t_ddp`` is the data-parallel compute time of one minibatch on one
    trainer (forward+backward+allreduce). At paper scale (A100, batch
    2000, fanout {10,25}) this is ~50 ms. ``link_bw`` is the per-trainer
    effective bandwidth of the RPC fetch path: Slingshot gives ~2.5 GB/s
    effective per trainer at full scale; our graphs (and therefore the
    per-minibatch fetch sets) are scaled down ~100x, so the default
    bandwidth is scaled by the same factor (~1 MB/s, i.e. ~100 MB/s
    effective TCP RPC bandwidth at full scale) to keep
    T_COMM / T_DDP in the paper's regime (baseline communication roughly
    comparable to compute, §5.1). ``alpha`` is the per-round RPC latency.
    """

    t_ddp: float = 0.050
    link_bw: float = 1e6
    alpha: float = 5e-4
    feature_bytes: int = 4

    def t_comm(self, fetched_nodes: int, feature_dim: int) -> float:
        if fetched_nodes == 0:
            return 0.0
        return self.alpha + fetched_nodes * feature_dim * self.feature_bytes / self.link_bw

    def t_comm_batch(self, fetched_nodes: np.ndarray, feature_dim: int) -> np.ndarray:
        """Vectorized :meth:`t_comm` over all trainer PEs at once (the
        single source of the formula for the vectorized runtime)."""
        fetched_nodes = np.asarray(fetched_nodes)
        return np.where(
            fetched_nodes > 0,
            self.alpha
            + fetched_nodes * feature_dim * self.feature_bytes / self.link_bw,
            0.0,
        )

    def step_time_batch(
        self,
        t_comm: np.ndarray,
        stalls: np.ndarray,
        inference_cost: np.ndarray,
        mode: str,
        t_ddp: np.ndarray | float | None = None,
        t_stall: float | None = None,
    ) -> np.ndarray:
        """The §4.5.3 async/sync step-time composition, all PEs at once.

        This is the **single** statement of the paper's formulas —
        ``async = max(T_DDP, T_COMM)`` (inference hidden) and
        ``sync = T_DDP + T_COMM + stalls * T_A/C`` for PEs whose
        controller pays inference (non-adaptive PEs overlap comm with
        compute in either mode). The legacy loop, the vectorized
        :class:`repro.runtime.stage.FetchStage` and the event engine's
        parity path all price steps through here, so the three cannot
        drift. ``t_ddp`` admits per-PE compute durations (the event
        engine's straggler axis) and ``t_stall`` re-prices one stall
        tick (its wall-clock agent axis); both default to the closed
        form's flat ``t_ddp`` constant.
        """
        t_ddp = self.t_ddp if t_ddp is None else t_ddp
        t_stall = self.t_ddp if t_stall is None else t_stall
        if mode == "sync":
            return np.where(
                np.asarray(inference_cost) > 0,
                t_ddp + t_comm + np.asarray(stalls) * t_stall,
                np.maximum(t_ddp, t_comm),
            )
        return np.maximum(t_ddp, t_comm)


@dataclass
class TrainerLog:
    pct_hits: list[float] = field(default_factory=list)
    comm_volume: list[int] = field(default_factory=list)
    comm_missed: list[int] = field(default_factory=list)
    occupancy: list[float] = field(default_factory=list)
    unique_remote: list[int] = field(default_factory=list)
    replaced: list[int] = field(default_factory=list)
    decisions: list[bool] = field(default_factory=list)
    step_time: list[float] = field(default_factory=list)
    # Feature-store streams (populated only when the store is enabled):
    # bytes the store actually moved vs the §4.5.3 accounting bytes, the
    # measured wall-clock of the step's gathers, and the
    # content-sensitive float64 sum of the delivered remote block.
    bytes_measured: list[int] = field(default_factory=list)
    bytes_modeled: list[int] = field(default_factory=list)
    fetch_seconds: list[float] = field(default_factory=list)
    feat_sums: list[float] = field(default_factory=list)


@dataclass
class RunResult:
    variant: str
    epoch_times: list[float]
    losses: list[float]
    accuracy: float
    logs: list[TrainerLog]
    controllers: list[Controller]
    graph_meta: list[GraphMeta]
    #: Event timeline of the run (``repro.sim.EventLog``) when priced by
    #: the event engine; None under the closed-form model.
    sim_events: object | None = None
    #: Recorded run trace (``repro.trace.Trace``) when the trainer was
    #: built with ``trace=...``; None otherwise.
    trace: object | None = None
    #: Flat telemetry summary (``TelemetrySession.summary()``) when the
    #: trainer was built with ``telemetry=...``; None otherwise.
    telemetry: dict | None = None

    # ---- aggregates used across the benchmark suite ------------------- #
    # Aggregates over an *empty* run (zero epochs / zero logged
    # minibatches) are NaN, not 0.0: a silent zero looks like a perfect
    # run in sweep artifacts, while NaN trips the CI gate
    # (``runtime.sweep.validate_rows``).
    @property
    def mean_epoch_time(self) -> float:
        return float(np.mean(self.epoch_times)) if self.epoch_times else float("nan")

    @property
    def mean_pct_hits(self) -> float:
        vals = [h for log in self.logs for h in log.pct_hits]
        return float(np.mean(vals)) if vals else float("nan")

    @property
    def total_comm(self) -> int:
        return int(sum(sum(log.comm_volume) for log in self.logs))

    @property
    def comm_per_minibatch(self) -> float:
        n = sum(len(log.comm_volume) for log in self.logs)
        return self.total_comm / n if n else float("nan")

    @property
    def steady_pct_hits(self) -> float:
        """Mean %-Hits over the last quarter of the run (post cold-start)."""
        vals = []
        for log in self.logs:
            n = len(log.pct_hits)
            vals.extend(log.pct_hits[max(n - n // 4, 1):])
        return float(np.mean(vals)) if vals else float("nan")

    def comm_p99(self) -> float:
        vals = [c for log in self.logs for c in log.comm_volume]
        return float(np.percentile(vals, 99)) if vals else float("nan")

    # ---- feature-store aggregates (0 / NaN when the store was off) ---- #
    @property
    def total_bytes_measured(self) -> int:
        return int(sum(sum(log.bytes_measured) for log in self.logs))

    @property
    def total_bytes_modeled(self) -> int:
        return int(sum(sum(log.bytes_modeled) for log in self.logs))

    @property
    def total_fetch_seconds(self) -> float:
        """Measured wall-clock spent in store gathers (cluster steps sum
        the per-step maximum across PEs, like epoch_times does)."""
        per_step = zip(*(log.fetch_seconds for log in self.logs))
        vals = [max(step) for step in per_step]
        return float(sum(vals)) if vals else float("nan")


class DistributedTrainer:
    """One experiment: (graph, partitioning, variant, controller, buffer)."""

    def __init__(
        self,
        parts: Partitioned,
        variant: str = "rudder",
        deciders: list | None = None,
        buffer_frac: float = 0.25,
        batch_size: int = 256,
        fanouts: tuple[int, ...] = (10, 25),
        epochs: int = 5,
        lr: float = 1e-2,
        hidden_dim: int = 64,
        mode: str = "async",
        interval: int = 32,
        warm_start: bool = True,
        train_model: bool = True,
        time_model: TimeModel | None = None,
        seed: int = 0,
        runtime: str = "vectorized",
        policy: str | scoring.ScoringPolicy = "rudder",
        topology: str | Topology | None = None,
        time_engine: str = "closed_form",
        stragglers: str | StragglerModel | None = None,
        congestion: str | CongestionModel | None = None,
        sim=None,
        trace: object = False,
        feature_store: object = False,
        device: object = False,
        readback_every: int = 1,
        telemetry: object = False,
        model: str = "sage",
        heads: int | None = None,
    ):
        if runtime not in ("vectorized", "legacy"):
            raise ValueError(
                f"runtime must be 'vectorized' or 'legacy', got {runtime!r}"
            )
        # Device-resident hot path (docs/ARCHITECTURE.md §"Device-resident
        # hot path"): False/None = staged numpy pipeline; True/"jnp" =
        # persistent jax device buffers + the fused jit'd oracle;
        # "pallas" = the fused Pallas megakernel (kernels/fused_step.py).
        # Streams stay bit-identical on every setting
        # (tests/test_fused_step.py).
        if device not in (False, None, True, "jnp", "pallas"):
            raise ValueError(
                "device must be False, True, 'jnp' or 'pallas', "
                f"got {device!r}"
            )
        if device and runtime == "legacy":
            raise ValueError("device mode requires runtime='vectorized'")
        if model not in MODELS:
            raise ValueError(f"model must be one of {sorted(MODELS)}, got {model!r}")
        if model == "sage":
            if heads is not None:
                raise ValueError("heads is a setting of model='gat'")
            if train_model and len(fanouts) != 2:
                raise ValueError(
                    f"GraphSAGE trains 2 layers on 2 hops, got fanouts {tuple(fanouts)}"
                )
        else:
            heads = 4 if heads is None else heads
            if not isinstance(heads, int) or heads < 1:
                raise ValueError(f"heads must be an int >= 1, got {heads!r}")
            if len(fanouts) < 2:
                raise ValueError(
                    f"GAT trains one layer per hop, at least 2, got fanouts {tuple(fanouts)}"
                )
        self.model = MODELS[model]
        self.device = device or False
        # K-step readback cadence for sweep runs: with device mode on and
        # K > 1, the driver pulls only a stacked (K, P, 4) counter block
        # every K launches instead of a per-step readback. Incompatible
        # with anything that consumes per-step id streams — the driver
        # raises (see repro.runtime.driver._check_cadence_eligible).
        if not isinstance(readback_every, (int, np.integer)) or isinstance(
            readback_every, bool
        ) or readback_every < 1:
            raise ValueError(
                f"readback_every must be an int >= 1, got {readback_every!r}"
            )
        if readback_every > 1 and not device:
            raise ValueError("readback_every > 1 requires device=...")
        self.readback_every = int(readback_every)
        if time_engine not in ("closed_form", "event"):
            raise ValueError(
                "time_engine must be 'closed_form' or 'event', "
                f"got {time_engine!r}"
            )
        self.parts = parts
        self.graph: Graph = parts.graph
        self.variant = variant
        self.runtime = runtime
        self.policy = scoring.make_policy(policy)
        self.buffer_frac = buffer_frac
        self.batch_size = batch_size
        self.epochs = epochs
        self.lr = lr
        self.mode = mode
        self.train_model = train_model
        self.tm = time_model or TimeModel()
        # Per-pair comm pricing (None keeps the flat §4.5.3 constants).
        if isinstance(topology, str):
            topology = make_topology(
                topology, parts.num_parts,
                link_bw=self.tm.link_bw, alpha=self.tm.alpha,
            )
        if topology is not None and topology.num_parts != parts.num_parts:
            raise ValueError(
                f"topology is {topology.num_parts}-way but the graph is "
                f"partitioned {parts.num_parts}-way"
            )
        self.topology = topology
        # Wall-clock model: closed-form §4.5.3 (default) or the event
        # simulator of repro.sim. Scenario presets resolve here so the
        # sweep can pass plain strings; a fresh engine is built per run
        # (make_time_engine) so event logs never leak across runs.
        if isinstance(stragglers, str):
            stragglers = (
                None
                if stragglers == "none"
                else make_stragglers(stragglers, parts.num_parts, seed=seed)
            )
        if isinstance(congestion, str):
            congestion = (
                None
                if congestion == "none"
                else make_congestion(
                    congestion, parts.num_parts, link_bw=self.tm.link_bw
                )
            )
        if time_engine == "closed_form" and (
            stragglers is not None or congestion is not None
        ):
            raise ValueError(
                "stragglers/congestion scenarios require time_engine='event' "
                "(the closed-form model cannot express them)"
            )
        self.time_engine = time_engine
        self.stragglers = stragglers
        self.congestion = congestion
        self.sim = sim
        self.last_time_engine = None
        # Trace capture (repro.trace): False/None = off (zero overhead),
        # True = record with a default recorder, or a TraceRecorder
        # instance (the CLI/sweep pass one carrying the full replayable
        # config). The finished Trace lands on self.last_trace.
        self.trace = trace
        self.last_trace = None
        # Telemetry plane (repro.telemetry): False/None = off (zero
        # overhead — no session is ever constructed); True = collect
        # into a fresh TelemetrySession; a TelemetrySession instance is
        # used as-is (single-use, like recorders). The finished session
        # lands on self.last_telemetry and its summary on
        # RunResult.telemetry. Never perturbs exact streams.
        self.telemetry = telemetry
        self.last_telemetry = None
        # Feature-store data plane (repro.store): False/None = modeled
        # bytes only; True = build a store over this graph's partitioned
        # features; a FeatureStore instance is used as-is. With the
        # store on, buffers and engine carry a real feature payload and
        # both runtimes move the bytes the accounting counts — without
        # changing any exact stream (the conformance contract of
        # tests/test_trace_golden.py).
        self.feature_store = None
        self._feature_table = None  # see feature_table()
        if feature_store:
            from ..store import FeatureStore

            self.feature_store = (
                feature_store
                if isinstance(feature_store, FeatureStore)
                else FeatureStore.for_partitions(parts)
            )
        self.rng = np.random.default_rng(seed)
        self.sampler = NeighborSampler(self.graph, fanouts)
        # Batched twin of the per-PE sampler: all P trainers' minibatches
        # advance in one pass (bit-identical draws; see SamplerPlane).
        self.sampler_plane = SamplerPlane(self.graph, fanouts)

        P = parts.num_parts
        self.graph_meta = [
            GraphMeta(
                name=self.graph.name,
                num_nodes=self.graph.num_nodes,
                num_edges=self.graph.num_edges,
                part_nodes=len(parts.local_nodes[p]),
                part_edges=parts.part_edges(p),
                num_partitions=P,
            )
            for p in range(P)
        ]

        # Halo (total remote nodes per partition): distinct 1-hop
        # neighbors homed elsewhere — the reference set for buffer sizing
        # ("5%/25% of remote nodes relative to total remote nodes per
        # partition", §5.1).
        self.halos = []
        for p in range(P):
            nodes = parts.local_nodes[p]
            nbrs = np.unique(
                np.concatenate(
                    [self.graph.neighbors(int(u)) for u in nodes]
                    or [np.array([], dtype=np.int64)]
                )
            )
            self.halos.append(nbrs[parts.part_of[nbrs] != p])

        # The degree policy weighs accesses by the node's (log) degree.
        node_weights = (
            scoring.degree_weights(self.graph.degree())
            if self.policy.use_weights
            else None
        )
        payload_dim = (
            self.graph.features.shape[1] if self.feature_store is not None else 0
        )
        self.buffers = [
            PersistentBuffer(
                capacity=max(int(len(self.halos[p]) * buffer_frac), 1),
                feature_dim=payload_dim,
                policy=self.policy,
                node_weights=node_weights,
                id_base=self.graph.id_base,
            )
            for p in range(P)
        ]
        # Vectorized twin of the per-PE buffers: one (P, C) array state.
        self.engine = PrefetchEngine(
            [b.capacity for b in self.buffers],
            policy=self.policy,
            node_weights=node_weights,
            feature_dim=payload_dim,
            id_base=self.graph.id_base,
        )

        # Controllers (one per trainer, as in the paper: each trainer has
        # its own prefetcher + daemon inference thread).
        self.controllers: list[Controller] = []
        for p in range(P):
            decider = None
            if variant == "rudder":
                if deciders is None:
                    raise ValueError("rudder variant needs deciders")
                decider = deciders[p % len(deciders)]
            self.controllers.append(
                make_controller(
                    variant,
                    graph=self.graph_meta[p],
                    decider=decider,
                    mode=mode,
                    interval=interval,
                    warm_start=warm_start,
                )
            )

        # MassiveGNN warm start: prefetch the highest-degree remote halo
        # nodes before training (§5.1 "Comparison with MassiveGNN").
        if variant == "massivegnn" and warm_start:
            deg = self.graph.degree()
            base = np.int64(self.graph.id_base)
            for p in range(P):
                halo = self.halos[p]
                top = halo[np.argsort(-deg[halo])][: self.buffers[p].capacity]
                # Buffer/engine/store ids live in the global id space.
                top = top + base
                n = self.buffers[p].insert(top)
                self.engine.insert(p, top)
                if self.feature_store is not None and n:
                    # Warm-started admissions place real rows too (top is
                    # unique and the buffer empty, so exactly top[:n]
                    # landed, in order, in both twins).
                    rows = self.feature_store.gather(top[:n])
                    self.buffers[p].fill_rows(top[:n], rows)
                    self.engine.place_rows(p, self.engine.last_slots[p], rows)

        self.local_train = [parts.local_train_nodes(p) for p in range(P)]
        self.mb_per_epoch = max(
            1,
            max(
                (len(t) + batch_size - 1) // batch_size
                for t in self.local_train
                if len(t)
            ),
        )

        if train_model:
            key = jax.random.PRNGKey(seed)
            self.params = self.model.init(
                key,
                self.graph.features.shape[1],
                hidden_dim,
                self.graph.num_classes,
                heads,
                len(fanouts),
            )

    # ------------------------------------------------------------------ #
    def _seed_batch(self, p: int, epoch: int, mb: int) -> np.ndarray:
        t = self.local_train[p]
        if len(t) == 0:
            return self.graph.train_nodes[: self.batch_size]
        perm = np.random.default_rng((epoch * 1000003 + p) ^ 0xC0FFEE).permutation(
            len(t)
        )
        start = (mb * self.batch_size) % len(t)
        idx = perm[start : start + self.batch_size]
        if len(idx) < min(self.batch_size, len(t)):
            idx = np.concatenate([idx, perm[: self.batch_size - len(idx)]])
        return t[idx]

    def feature_table(self):
        """``(table, loc)``: the feature table training reads, on the
        device, and the int32 map from a node's local id to its row
        (None where the id is the row).

        Uploaded once, at the first training step, and kept for every
        later ``run()``: ``graph.features`` with its rows padded to whole
        lanes, so that the TPU lays the table out row by row and a row
        gather reads it in place; or with a feature store the store's own
        :meth:`FeatureStore.device_view`, asked for at every step so that
        a :meth:`FeatureStore.poke` reaches training. An upload counts
        ``device.h2d_bytes`` and ``train.table_uploads``.
        """
        store = self.feature_store
        if store is not None:
            fresh = not store.has_device_view
            table, loc = store.device_view()
        else:
            fresh = self._feature_table is None
            if fresh:
                features = self.graph.features
                pad = -features.shape[1] % LANES
                self._feature_table = jax.device_put(
                    np.pad(features, ((0, 0), (0, pad)))
                )
            table, loc = self._feature_table, None
        if fresh:
            tel.count(
                "device.h2d_bytes",
                table.nbytes + (0 if loc is None else loc.nbytes),
            )
            tel.count("train.table_uploads", 1)
        return table, loc

    def feature_rows(self, table, loc, ids) -> tuple[Rows, ...]:
        """:class:`Rows` in ``table`` of the uploaded seed block and of
        each hop's (all of :func:`index_blocks` but the labels)."""
        width = self.graph.features.shape[1]
        return tuple(Rows(table, loc, idx, width) for idx in ids)

    # ------------------------------------------------------------------ #
    def make_time_engine(self):
        """Build a fresh per-run wall-clock engine (``repro.sim``).

        Both runtimes call this at the top of a run; the returned engine
        also stays reachable as ``self.last_time_engine`` so callers can
        inspect the event timeline after ``run()``.
        """
        from .. import sim

        engine = sim.make_time_engine(
            self.time_engine,
            tm=self.tm,
            mode=self.mode,
            inference_cost=np.array(
                [c.inference_cost for c in self.controllers],
                dtype=np.float64,
            ),
            feature_dim=self.graph.features.shape[1],
            num_pes=self.parts.num_parts,
            topology=self.topology,
            stragglers=self.stragglers,
            congestion=self.congestion,
            config=self.sim,
            total_steps=self.epochs * self.mb_per_epoch,
        )
        self.last_time_engine = engine
        return engine

    # ------------------------------------------------------------------ #
    def make_trace_recorder(self):
        """Resolve the ``trace`` flag to a recorder (or None when off).

        Both runtimes call this at the top of a run. A pre-built
        :class:`repro.trace.TraceRecorder` is used as-is (single-use —
        recorders are per-run, like time engines); ``trace=True`` builds
        a fresh default recorder from the trainer's own axes.
        """
        if not self.trace:
            return None
        from ..trace import TraceRecorder

        if isinstance(self.trace, TraceRecorder):
            return self.trace
        return TraceRecorder.for_trainer(self)

    # ------------------------------------------------------------------ #
    def make_telemetry(self):
        """Resolve the ``telemetry`` flag to a session (or None when off).

        Mirrors :meth:`make_trace_recorder`: a pre-built
        :class:`repro.telemetry.TelemetrySession` is used as-is,
        ``telemetry=True`` builds a fresh default session.
        """
        if not self.telemetry:
            return None
        from ..telemetry import TelemetrySession

        if isinstance(self.telemetry, TelemetrySession):
            return self.telemetry
        return TelemetrySession(label=self.variant)

    # ------------------------------------------------------------------ #
    def run(self) -> RunResult:
        """Execute the experiment (vectorized runtime by default).

        With ``telemetry=...`` set, the run executes under an active
        :class:`repro.telemetry.TelemetrySession`; the session lands on
        ``self.last_telemetry`` and its summary on the result.
        """
        session = self.make_telemetry()
        if session is None:
            return self._run_impl()
        from .. import telemetry as tel

        with tel.active(session):
            with session.tracer.span("run", plane="runtime"):
                result = self._run_impl()
        session.meta.setdefault("variant", self.variant)
        session.meta.setdefault("mode", self.mode)
        session.meta.setdefault("num_pes", self.parts.num_parts)
        self.last_telemetry = session
        result.telemetry = session.summary()
        return result

    def _run_impl(self) -> RunResult:
        if self.runtime == "vectorized":
            from ..runtime.driver import run_vectorized

            return run_vectorized(self)
        return self.run_legacy()

    def run_legacy(self) -> RunResult:
        """Reference implementation: one PE at a time, one Python loop.

        Kept as the semantic oracle for the vectorized runtime
        (``tests/test_runtime_parity.py``); benchmarks use :meth:`run`.
        """
        from ..sim import build_step_comm

        P = self.parts.num_parts
        logs = [TrainerLog() for _ in range(P)]
        epoch_times: list[float] = []
        losses: list[float] = []
        time_engine = self.make_time_engine()
        recorder = self.make_trace_recorder()

        # Pipeline staleness: ReplaceandFetch overlaps with training, so a
        # replacement round admits the miss set of the *previous*
        # minibatch (Algorithm 1 queues the next minibatch before the
        # decision lands). Frequent replacement therefore keeps admitting
        # one-round-old tail nodes — churn the adaptive controller avoids.
        prev_missed = [np.array([], dtype=np.int64) for _ in range(P)]
        empty = np.array([], dtype=np.int64)

        for epoch in range(self.epochs):
            epoch_time = 0.0
            for mb in range(self.mb_per_epoch):
                grads_acc = None
                loss_acc = 0.0
                missed_sets: list[np.ndarray] = []
                placed_sets: list[np.ndarray] = []
                stall_ticks: list[float] = []
                # Trace-only per-PE collections (references, not copies;
                # empty work when capture is off).
                seed_sets: list[np.ndarray] = []
                remote_sets: list[np.ndarray] = []
                hit_counts: list[int] = []
                occ_pre: list[float] = []
                # Feature-store per-PE captures (hit rows must be read at
                # lookup time — replacement may overwrite their slots).
                hit_mask_sets: list[np.ndarray] = []
                hit_row_sets: list[np.ndarray] = []
                _step_sp = tel.begin("step", plane="runtime")
                for p in range(P):
                    _pe_sp = tel.begin("pe_step", pe=p, plane="runtime")
                    ctrl = self.controllers[p]
                    buf = self.buffers[p]
                    batch = self._seed_batch(p, epoch, mb)
                    minibatch = self.sampler.sample(batch, self.rng)
                    remote = unique_remote(
                        minibatch, self.parts.part_of, p,
                        id_base=self.graph.id_base,
                    )
                    n_remote = len(remote)

                    slots = None
                    if ctrl.uses_buffer and buf.capacity > 0:
                        hit_mask, slots = buf.lookup(remote)
                        missed = remote[~hit_mask]
                        hits = int(hit_mask.sum())
                        pct_hits = (
                            100.0 * hits / n_remote if n_remote else 100.0
                        )
                    else:
                        hit_mask = np.zeros(n_remote, dtype=bool)
                        missed = remote
                        hits = 0
                        pct_hits = 0.0
                    if self.feature_store is not None:
                        hit_mask_sets.append(hit_mask)
                        hit_row_sets.append(
                            buf.features[slots[hit_mask]]
                            if slots is not None
                            else np.zeros(
                                (0, self.feature_store.feature_dim),
                                dtype=np.float32,
                            )
                        )
                    if recorder is not None:
                        seed_sets.append(batch)
                        remote_sets.append(remote)
                        hit_counts.append(hits)
                        occ_pre.append(buf.occupancy)

                    comm = len(missed)
                    metrics = Metrics(
                        minibatch=mb,
                        total_minibatches=self.mb_per_epoch,
                        epoch=epoch,
                        total_epochs=self.epochs,
                        pct_hits=pct_hits,
                        comm_volume=comm,
                        replaced_pct=(
                            100.0 * logs[p].replaced[-1] / buf.capacity
                            if logs[p].replaced and buf.capacity
                            else 0.0
                        ),
                        buffer_occupancy=buf.occupancy,
                        buffer_capacity=buf.capacity,
                    )
                    replace = ctrl.should_replace(metrics)
                    if ctrl.uses_buffer:
                        buf.end_round()
                    replaced = 0
                    if replace and ctrl.uses_buffer:
                        replaced = buf.replace(prev_missed[p])
                    prev_missed[p] = missed
                    # Replacement traffic: ReplaceandFetch (Alg. 1 line 14)
                    # issues a separate aggregated RPC for the nodes pulled
                    # into the persistent buffer — counted as communication
                    # (this is why over-replacement blows up comm, Fig. 20).
                    comm += replaced

                    logs[p].pct_hits.append(pct_hits)
                    logs[p].comm_volume.append(comm)
                    logs[p].comm_missed.append(len(missed))
                    logs[p].occupancy.append(buf.occupancy)
                    logs[p].unique_remote.append(n_remote)
                    logs[p].replaced.append(replaced)
                    logs[p].decisions.append(bool(replace))

                    # Exact per-PE communication artifacts for the time
                    # engine (priced after the PE loop, whole cluster at
                    # once — link contention couples the PEs).
                    missed_sets.append(missed)
                    placed_sets.append(
                        buf.last_placed
                        if replace and ctrl.uses_buffer
                        else empty
                    )
                    stall_ticks.append(ctrl.step_stall())

                    if self.train_model:
                        *ids, labels = jax.device_put(index_blocks(minibatch))
                        loss, grads = self.model.grads(
                            self.params,
                            self.feature_rows(*self.feature_table(), ids),
                            labels,
                        )
                        loss_acc += float(loss) / P
                        grads_acc = (
                            grads
                            if grads_acc is None
                            else jax.tree_util.tree_map(
                                lambda a, b: a + b, grads_acc, grads
                            )
                        )
                    tel.end(_pe_sp)

                # Wall-clock pricing of the exact streams (§4.5.3 closed
                # form or the event simulator), then the gradient sync
                # across trainers (bulk-synchronous step barrier).
                step_times = time_engine.step(
                    build_step_comm(
                        missed_sets,
                        placed_sets,
                        self.parts.part_of,
                        P,
                        time_engine.needs_pairs,
                        id_base=self.graph.id_base,
                    ),
                    np.asarray(stall_ticks, dtype=np.float64),
                )
                for p in range(P):
                    logs[p].step_time.append(float(step_times[p]))
                epoch_time += float(step_times.max())

                # Feature-store data plane: serve the exact miss/placement
                # streams with real gathers (mirrors FetchStage.commit's
                # _serve_features — two batched gathers after the PE loop,
                # hit rows already captured at lookup time above).
                store_kwargs: dict = {}
                if self.feature_store is not None:
                    store = self.feature_store
                    F = store.feature_dim
                    miss_g = store.gather_batch(missed_sets)
                    placed_g = store.gather_batch(placed_sets)
                    fetch_seconds = miss_g.seconds + placed_g.seconds
                    feat_sums = np.zeros(P, dtype=np.float64)
                    bytes_measured = np.zeros(P, dtype=np.int64)
                    bytes_modeled = np.zeros(P, dtype=np.int64)
                    for p in range(P):
                        if len(placed_sets[p]):
                            self.buffers[p].fill_rows(
                                placed_sets[p], placed_g.blocks[p]
                            )
                        block = np.empty(
                            (len(hit_mask_sets[p]), F), dtype=np.float32
                        )
                        block[hit_mask_sets[p]] = hit_row_sets[p]
                        block[~hit_mask_sets[p]] = miss_g.blocks[p]
                        feat_sums[p] = block.sum(dtype=np.float64)
                        bytes_measured[p] = (
                            miss_g.blocks[p].nbytes + placed_g.blocks[p].nbytes
                        )
                        bytes_modeled[p] = (
                            logs[p].comm_volume[-1] * F * self.tm.feature_bytes
                        )
                        logs[p].bytes_measured.append(int(bytes_measured[p]))
                        logs[p].bytes_modeled.append(int(bytes_modeled[p]))
                        logs[p].fetch_seconds.append(float(fetch_seconds))
                        logs[p].feat_sums.append(float(feat_sums[p]))
                    store_kwargs = dict(
                        feat_sums=feat_sums,
                        bytes_measured=bytes_measured,
                        bytes_modeled=bytes_modeled,
                        fetch_time_measured=np.full(
                            P, fetch_seconds, dtype=np.float64
                        ),
                    )
                if recorder is not None:
                    recorder.record_step(
                        seeds=seed_sets,
                        remote=remote_sets,
                        missed=missed_sets,
                        placed=placed_sets,
                        decisions=[logs[p].decisions[-1] for p in range(P)],
                        stalls=np.asarray(stall_ticks, dtype=np.float64),
                        pct_hits=[logs[p].pct_hits[-1] for p in range(P)],
                        hits=hit_counts,
                        n_remote=[logs[p].unique_remote[-1] for p in range(P)],
                        replaced=[logs[p].replaced[-1] for p in range(P)],
                        total_comm=[logs[p].comm_volume[-1] for p in range(P)],
                        occupancy_pre=occ_pre,
                        occupancy_post=[logs[p].occupancy[-1] for p in range(P)],
                        step_times=step_times,
                        controllers=self.controllers,
                        **store_kwargs,
                    )
                if self.train_model and grads_acc is not None:
                    grads_mean = jax.tree_util.tree_map(
                        lambda g: g / P, grads_acc
                    )
                    self.params = jax.tree_util.tree_map(
                        lambda prm, g: prm - self.lr * g, self.params, grads_mean
                    )
                    losses.append(loss_acc)
                tel.end(_step_sp)
            epoch_times.append(epoch_time)

        from ..runtime.driver import _final_accuracy

        accuracy = _final_accuracy(self)

        trace = None
        if recorder is not None:
            trace = recorder.finalize(epoch_times, time_engine.events)
            self.last_trace = trace

        return RunResult(
            variant=self.variant,
            epoch_times=epoch_times,
            losses=losses,
            accuracy=accuracy,
            logs=logs,
            controllers=self.controllers,
            graph_meta=self.graph_meta,
            sim_events=time_engine.events,
            trace=trace,
        )


def collect_traces(
    parts: Partitioned,
    buffer_frac: float = 0.25,
    batch_size: int = 256,
    epochs: int = 3,
    seed: int = 0,
) -> tuple[np.ndarray, np.ndarray]:
    """Trace-only mode (§4.4): run DistDGL+fixed with training disabled,
    record per-minibatch features and S'-labels for offline classifier
    training. Returns (X, y)."""
    from ..core.classifiers import featurize, label_traces

    trainer = DistributedTrainer(
        parts,
        variant="fixed",
        buffer_frac=buffer_frac,
        batch_size=batch_size,
        epochs=epochs,
        train_model=False,
        seed=seed,
    )
    result = trainer.run()
    X_rows, y_rows = [], []
    for p, log in enumerate(result.logs):
        hits = np.array(log.pct_hits)
        comm = np.array(log.comm_volume, dtype=np.float64)
        repl = np.array(log.replaced, dtype=np.float64)
        labels = label_traces(hits, comm, repl)
        cap = trainer.buffers[p].capacity
        prev = None
        recent: list[float] = []
        recent_c: list[int] = []
        for i in range(len(hits)):
            m = Metrics(
                minibatch=i % trainer.mb_per_epoch,
                total_minibatches=trainer.mb_per_epoch,
                epoch=i // trainer.mb_per_epoch,
                total_epochs=epochs,
                pct_hits=float(hits[i]),
                comm_volume=int(comm[i]),
                replaced_pct=100.0 * repl[i] / cap if cap else 0.0,
                buffer_occupancy=float(log.occupancy[i]),
                buffer_capacity=cap,
            )
            recent.append(float(hits[i]))
            recent_c.append(int(comm[i]))
            X_rows.append(featurize(m, prev, recent[-16:], recent_c[-16:]))
            y_rows.append(labels[i])
            prev = m
    return np.stack(X_rows), np.array(y_rows, dtype=np.float32)
