"""Vectorized multi-PE persistent-buffer state (the prefetch engine).

One :class:`PrefetchEngine` replaces the list of per-trainer
:class:`repro.core.buffer.PersistentBuffer` objects: membership, scores,
validity and per-round access marks for *all* P trainer PEs live in
dense ``(P, C)`` arrays (C = max buffer capacity across PEs; slots past
a PE's own capacity are permanent padding). Lookups across every PE are
answered by a single sort + ``searchsorted`` over offset-disambiguated
keys, and the scoring round is one elementwise pass — optionally the
multi-PE Pallas kernel :func:`repro.kernels.score_update_batch`.

State-transition semantics are *bit-identical* to ``PersistentBuffer``
(same slot ordering, same float32 score arithmetic, same free-then-stale
replacement order), which is what lets the vectorized driver reproduce
the legacy per-trainer loop's hit/miss/byte counts and decision streams
exactly — see ``tests/test_runtime_parity.py`` and
``docs/ARCHITECTURE.md``.

:class:`DeviceEngine` is the device-resident twin: the same ``(P, C)``
state held as persistent jax arrays and advanced one fused
score→replace→probe launch per step
(:func:`repro.kernels.ops.fused_step_batch`), with only the compact
per-query / per-candidate outputs pulled to host. Enabled via
``DistributedTrainer(device=...)``; semantics and streams stay
bit-identical to this class (``tests/test_fused_step.py``,
``docs/KERNELS.md#fused_step``, ``docs/ARCHITECTURE.md`` §"Device-
resident hot path").
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from .. import telemetry as tel
from ..core import scoring
from ..core.buffer import _unique_preserve_order


@dataclass
class EngineStats:
    """Per-PE counters, mirror of ``core.buffer.BufferStats``."""

    num_pes: int
    lookups: np.ndarray = field(default=None)
    hits: np.ndarray = field(default=None)
    misses: np.ndarray = field(default=None)
    replaced_total: np.ndarray = field(default=None)
    replacement_rounds: np.ndarray = field(default=None)
    skipped_rounds: np.ndarray = field(default=None)

    def __post_init__(self):
        for name in (
            "lookups",
            "hits",
            "misses",
            "replaced_total",
            "replacement_rounds",
            "skipped_rounds",
        ):
            if getattr(self, name) is None:
                setattr(self, name, np.zeros(self.num_pes, dtype=np.int64))

    def hit_rate(self) -> np.ndarray:
        # NaN (not 0.0) for PEs that never looked anything up — the
        # NaN-on-empty policy of RunResult's aggregates: a silent zero
        # reads as "all misses", NaN trips the sweep gate.
        return np.where(
            self.lookups > 0, self.hits / np.maximum(self.lookups, 1), np.nan
        )


class PrefetchEngine:
    """All trainer-PE buffers as one batched array state.

    Parameters
    ----------
    capacities:
        Per-PE buffer capacity. Internally padded to ``C = max(capacities)``;
        padding slots are never valid and never free.
    use_kernels:
        Route the scoring round through the multi-PE Pallas kernel
        (``repro.kernels.score_policy_update_batch``). The numpy path is
        the default on CPU — interpret-mode Pallas trades speed for
        fidelity to the TPU lowering; both produce bit-identical float32
        scores.
    policy:
        Scoring/eviction policy (name or :class:`repro.core.scoring.
        ScoringPolicy`) applied to every PE; default is the paper's
        ``rudder`` policy. Same contract as
        ``PersistentBuffer(policy=...)``.
    node_weights:
        Optional per-node access weights indexed by *local* node index
        (the ``degree`` policy's input); resolved to per-slot weights at
        insertion time. Buffer ids are global (``id_base`` + local), so
        placement subtracts ``id_base`` before the gather.
    id_base:
        Global id of local node 0 (``Graph.id_base``). All ids entering
        the engine (queries, candidates) are global; only per-node
        weight lookups need the local offset.
    feature_dim:
        If > 0, a dense feature payload ``(P, C, feature_dim)`` float32
        rides alongside membership (the feature-store data plane:
        admissions place real rows via :meth:`place_rows`, hits are
        served from the payload). 0 keeps the engine id-only.
    """

    def __init__(
        self,
        capacities: list[int],
        use_kernels: bool = False,
        policy: str | scoring.ScoringPolicy = "rudder",
        node_weights: np.ndarray | None = None,
        feature_dim: int = 0,
        id_base: int = 0,
    ):
        self.capacity = np.asarray(capacities, dtype=np.int64)
        if (self.capacity < 0).any():
            raise ValueError("capacities must be >= 0")
        self.num_pes = P = len(capacities)
        self.max_capacity = C = int(self.capacity.max(initial=1)) if P else 1
        self.use_kernels = use_kernels
        self.policy = scoring.make_policy(policy)
        self._node_weights = node_weights
        self.id_base = int(id_base)
        self.ids = np.full((P, C), -1, dtype=np.int64)
        self.scores = np.zeros((P, C), dtype=np.float32)
        self.weights = np.ones((P, C), dtype=np.float32)
        self.valid = np.zeros((P, C), dtype=bool)
        self.accessed = np.zeros((P, C), dtype=bool)
        # Slots at or past a PE's own capacity are permanent padding.
        self.in_capacity = np.arange(C)[None, :] < self.capacity[:, None]
        self.stats = EngineStats(P)
        # Nodes admitted by the most recent replace_round (per PE): the
        # topology cost model prices their fetch RPCs by home partition.
        self.last_placed: list[np.ndarray] = [
            np.array([], dtype=np.int64) for _ in range(P)
        ]
        # Feature payload (feature-store data plane). last_hit_slots /
        # last_slots let the fetch stage serve hit rows from the payload
        # and fill newly admitted slots with real rows.
        self.feature_dim = int(feature_dim)
        self.payload = (
            np.zeros((P, C, self.feature_dim), dtype=np.float32)
            if self.feature_dim > 0
            else None
        )
        #: Per-PE slots of the most recent lookup's hits, in query order.
        self.last_hit_slots: list[np.ndarray] = [
            np.array([], dtype=np.int64) for _ in range(P)
        ]
        #: Per-PE slots filled by the most recent placement round
        #: (aligned with ``last_placed`` after ``replace_round``).
        self.last_slots: list[np.ndarray] = [
            np.array([], dtype=np.int64) for _ in range(P)
        ]

    # ------------------------------------------------------------------ #
    # introspection
    # ------------------------------------------------------------------ #
    def size(self) -> np.ndarray:
        return self.valid.sum(axis=1)

    def occupancy(self) -> np.ndarray:
        return np.where(
            self.capacity > 0, self.size() / np.maximum(self.capacity, 1), 0.0
        )

    def ids_snapshot(self, p: int) -> np.ndarray:
        return self.ids[p][self.valid[p]].copy()

    def scores_snapshot(self, p: int) -> np.ndarray:
        return self.scores[p, : int(self.capacity[p])].copy()

    # ------------------------------------------------------------------ #
    # batched membership
    # ------------------------------------------------------------------ #
    def _membership(
        self, queries: np.ndarray, rows: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """Batched multi-PE membership test.

        ``queries[k]`` is a node id asked of PE ``rows[k]``. Returns
        ``(hit_mask, flat_slots)`` where ``flat_slots[k] = p * C + slot``
        for hits and -1 otherwise. One sort + one searchsorted answers
        every PE's lookup at once: keys are disambiguated by a per-PE
        offset larger than any node id, so ids never collide across PEs.
        """
        hit = np.zeros(len(queries), dtype=bool)
        flat_slots = np.full(len(queries), -1, dtype=np.int64)
        if len(queries) == 0 or not self.valid.any():
            return hit, flat_slots
        offset = int(max(self.ids.max(), queries.max(initial=0), 0)) + 2
        # Invalid slots get key `offset - 1` (never a real node id).
        keys = np.where(self.valid, self.ids, offset - 1)
        keys = keys + np.arange(self.num_pes, dtype=np.int64)[:, None] * offset
        order = np.argsort(keys, axis=None, kind="stable")
        flat_keys = keys.ravel()[order]
        q = queries.astype(np.int64) + rows.astype(np.int64) * offset
        pos = np.searchsorted(flat_keys, q)
        pos_c = np.minimum(pos, flat_keys.size - 1)
        hit = flat_keys[pos_c] == q
        flat_slots[hit] = order[pos_c[hit]]
        return hit, flat_slots

    def lookup(
        self, remote: list[np.ndarray], active: np.ndarray
    ) -> tuple[list[np.ndarray], list[np.ndarray]]:
        """Batched lookup of per-PE remote fetch sets.

        ``remote[p]`` is PE p's unique sampled remote ids; ``active[p]``
        gates whether the PE consults its buffer this round (inactive
        PEs — e.g. the no-prefetch baseline — fetch everything). Returns
        ``(hit_masks, missed)`` per PE; hits are marked accessed for the
        scoring round and the per-PE hit statistics are updated, exactly
        as ``PersistentBuffer.lookup`` does one PE at a time.
        """
        P = self.num_pes
        lengths = np.array(
            [len(remote[p]) if active[p] else 0 for p in range(P)], dtype=np.int64
        )
        rows = np.repeat(np.arange(P, dtype=np.int64), lengths)
        queries = (
            np.concatenate([remote[p] for p in range(P) if active[p] and len(remote[p])])
            if lengths.sum()
            else np.array([], dtype=np.int64)
        )
        hit, flat_slots = self._membership(queries, rows)
        self.last_hit_slots = [np.array([], dtype=np.int64) for _ in range(P)]
        if hit.any():
            self.accessed.ravel()[flat_slots[hit]] = True
            hit_rows = rows[hit]
            hit_slots = flat_slots[hit] - hit_rows * self.max_capacity
            for p in np.unique(hit_rows):
                self.last_hit_slots[p] = hit_slots[hit_rows == p]
        self.stats.lookups += lengths
        hits_per_pe = np.bincount(rows[hit], minlength=P) if len(rows) else np.zeros(
            P, dtype=np.int64
        )
        self.stats.hits += hits_per_pe
        self.stats.misses += lengths - hits_per_pe
        bounds = np.cumsum(lengths)[:-1]
        hit_masks = np.split(hit, bounds)
        out_masks, missed = [], []
        for p in range(P):
            if active[p]:
                out_masks.append(hit_masks[p])
                missed.append(remote[p][~hit_masks[p]])
            else:
                out_masks.append(np.zeros(len(remote[p]), dtype=bool))
                missed.append(remote[p])
        return out_masks, missed

    # ------------------------------------------------------------------ #
    # scoring round
    # ------------------------------------------------------------------ #
    def end_round(self, active: np.ndarray) -> None:
        """Close the sampling round for ``active`` PEs: one batched
        scoring pass (+1 on access, x0.95 idle) and reset access marks."""
        if not active.any():
            return
        weights = self.weights if self.policy.use_weights else None
        if self.use_kernels:
            from ..kernels import ops

            kc = self.policy.kernel_constants()
            kc.pop("initial_score")  # scoring pass never places slots
            new, _ = ops.score_policy_update_batch(
                self.scores, self.accessed, weights, **kc
            )
            new = np.asarray(new, dtype=np.float32)
        else:
            new = self.policy.update(self.scores, self.accessed, weights)
        mask = active[:, None] & self.valid
        self.scores = np.where(mask, new, self.scores).astype(np.float32)
        self.accessed[active] = False

    # ------------------------------------------------------------------ #
    # insertion / replacement
    # ------------------------------------------------------------------ #
    def insert(self, p: int, node_ids: np.ndarray) -> int:
        """Fill PE p's free slots (no eviction) — warm-start path."""
        node_ids = _unique_preserve_order(np.asarray(node_ids, dtype=np.int64))
        node_ids = node_ids[~np.isin(node_ids, self.ids[p][self.valid[p]])]
        free = np.nonzero(~self.valid[p] & self.in_capacity[p])[0]
        n = min(len(free), len(node_ids))
        if n == 0:
            return 0
        self._place(p, free[:n], node_ids[:n])
        return n

    def replace_round(
        self, candidates: list[np.ndarray], do_replace: np.ndarray
    ) -> np.ndarray:
        """One replacement round across all PEs.

        ``candidates[p]`` is the admission set (the previous minibatch's
        miss set — Algorithm 1 queues the next minibatch before the
        decision lands); ``do_replace[p]`` is the controller's decision.
        Free slots are filled first, then stale slots (score < 0.95), in
        ascending slot order — the exact ``PersistentBuffer.replace``
        semantics. Returns the number of nodes newly placed per PE.

        Membership filtering of every PE's candidate set happens in one
        batched query; the slot-mask computation (free / stale) is one
        array pass over ``(P, C)``; only the final ragged scatter is a
        short per-PE loop.
        """
        P = self.num_pes
        replaced = np.zeros(P, dtype=np.int64)
        self.last_placed = [np.array([], dtype=np.int64) for _ in range(P)]
        self.last_slots = [np.array([], dtype=np.int64) for _ in range(P)]
        todo = [p for p in range(P) if do_replace[p]]
        if not todo:
            return replaced
        cands = {p: _unique_preserve_order(np.asarray(candidates[p], dtype=np.int64))
                 for p in todo}
        lengths = np.array([len(cands[p]) for p in todo], dtype=np.int64)
        rows = np.repeat(np.asarray(todo, dtype=np.int64), lengths)
        queries = (
            np.concatenate([cands[p] for p in todo])
            if lengths.sum()
            else np.array([], dtype=np.int64)
        )
        member, _ = self._membership(queries, rows)
        fresh = np.split(~member, np.cumsum(lengths)[:-1])
        free_mask = ~self.valid & self.in_capacity
        stale_m = self.valid & self.policy.stale(self.scores)
        for k, p in enumerate(todo):
            cand = cands[p][fresh[k]]
            free = np.nonzero(free_mask[p])[0]
            stale = np.nonzero(stale_m[p])[0]
            slots = np.concatenate([free, stale])
            n = min(len(slots), len(cand))
            if n == 0:
                self.stats.skipped_rounds[p] += 1
                continue
            self._place(p, slots[:n], cand[:n])
            self.last_placed[p] = cand[:n]
            self.stats.replaced_total[p] += n
            self.stats.replacement_rounds[p] += 1
            replaced[p] = n
        return replaced

    def _place(self, p: int, slots: np.ndarray, ids: np.ndarray) -> None:
        self.ids[p, slots] = ids
        self.scores[p, slots] = np.float32(self.policy.initial_score)
        if self._node_weights is not None:
            self.weights[p, slots] = self._node_weights[ids - self.id_base]
        self.valid[p, slots] = True
        self.accessed[p, slots] = False
        self.last_slots[p] = np.asarray(slots, dtype=np.int64)

    def place_rows(self, p: int, slots: np.ndarray, rows: np.ndarray) -> None:
        """Fill PE p's payload slots with real feature rows (the
        feature-store admission path: ids land via ``insert`` /
        ``replace_round``, rows via the store gather that follows)."""
        if self.payload is None:
            raise ValueError("engine has no payload (feature_dim=0)")
        if len(slots) != len(rows):
            raise ValueError(f"{len(slots)} slots != {len(rows)} rows")
        if len(slots):
            self.payload[p, np.asarray(slots, dtype=np.int64)] = rows

    def hit_rows(self, p: int) -> np.ndarray:
        """Payload rows of the most recent lookup's hits for PE p, in
        query order (empty ``(0, F)`` when the PE had no hits)."""
        if self.payload is None:
            raise ValueError("engine has no payload (feature_dim=0)")
        return self.payload[p, self.last_hit_slots[p]]


@dataclass
class FusedStepOut:
    """Host-visible outputs of one :meth:`DeviceEngine.fused_step` launch."""

    hit_masks: list[np.ndarray]    # per PE, aligned with its query list
    missed: list[np.ndarray]       # per PE, int64 miss ids (query order)
    hits: np.ndarray               # (P,) int64
    hit_slots: list[np.ndarray]    # per PE, slots of the hits (query order)
    replaced: np.ndarray           # (P,) int64 — nodes newly placed
    placed: list[np.ndarray]       # per PE, int64 placed ids (cand order)
    placed_slots: list[np.ndarray] # per PE, slots filled (aligned w/ placed)
    n_valid: np.ndarray            # (P,) int64 post-round occupancy counts


@dataclass
class FrontierStepOut(FusedStepOut):
    """:class:`FusedStepOut` of a single-launch frontier step
    (:meth:`DeviceEngine.fused_step_raw`), which additionally derives
    the deduped remote query sets on device — the host never sees the
    raw frontier again after the upload."""

    remote: list[np.ndarray] = None   # per PE, int64 unique remote ids (sorted)
    n_remote: np.ndarray = None       # (P,) int64 remote query counts


def _bucket(n: int, q: int = 64) -> int:
    """Round a ragged dimension up to a bucket so jit recompiles O(log)
    times, not once per distinct minibatch shape."""
    return max(q, -(-n // q) * q)


def _split_by_counts(flat: np.ndarray, counts: np.ndarray) -> list[np.ndarray]:
    """Split a flat array into per-PE views by segment lengths (plain
    slicing — ``np.split`` pays a swapaxes per segment, which dominates
    the fused step's host time at P=256)."""
    ends = np.cumsum(counts)
    starts = ends - counts
    return [flat[a:b] for a, b in zip(starts, ends)]


class DeviceEngine:
    """Device-resident twin of :class:`PrefetchEngine` (the fused hot path).

    Construction snapshots a warm-started ``PrefetchEngine`` into
    persistent jax device arrays (ids int32, scores float32, valid /
    accessed / in-capacity masks, optional degree weights and feature
    payload) and from then on advances the whole cluster's buffer state
    one fused score→replace→probe launch per training step
    (:func:`repro.kernels.ops.fused_step_batch` — jnp oracle by default,
    Pallas kernel with ``backend="pallas"``). Only O(P·(M+K)) per-step
    outputs cross back to host: hit masks/slots, placed ids/slots and
    occupancy counts; the ``(P, C)`` state never round-trips.

    Statistics are *shared* with the source engine (``self.stats is
    engine.stats``), so ``trainer.engine.stats`` stays live in device
    mode; :meth:`sync_to_engine` writes the array state back for
    post-run introspection and state-equality tests.

    Semantics are bit-identical to the staged numpy pipeline
    (``lookup`` → ``end_round`` → ``replace_round``) — the parity
    contract of ``tests/test_fused_step.py`` and the golden traces.
    Narrow mode stores ids as a single int32 plane and serves id
    universes up to :data:`repro.kernels.ops.INT32_ID_MAX`; beyond that
    (or whenever ``id_base`` is nonzero) the engine auto-upgrades to
    **wide mode** — every id rides as an ``(hi, lo)`` int32 word pair
    (``docs/KERNELS.md`` §"Wide-id encoding") up to
    :data:`repro.kernels.ops.WIDE_ID_MAX` (~2^61). Ids beyond the wide
    bound raise at construction; the staged path has no limit.
    """

    def __init__(
        self,
        engine: PrefetchEngine,
        backend: str = "jnp",
        part_of: np.ndarray | None = None,
        id_base: int | None = None,
    ):
        import jax.numpy as jnp

        from ..kernels import ops

        if backend not in ("jnp", "pallas"):
            raise ValueError(
                f"backend must be 'jnp' or 'pallas', got {backend!r}"
            )
        self.id_base = int(
            engine.id_base if id_base is None else id_base
        )
        max_known = int(engine.ids.max()) if engine.ids.size else -1
        # Any nonzero base puts the whole id universe at or above it.
        max_known = max(max_known, self.id_base)
        if part_of is not None:
            # The id universe upper bound: every global id the run can
            # produce is id_base + a local index into part_of.
            max_known = max(max_known, self.id_base + len(part_of) - 1)
        self.wide = bool(self.id_base) or not ops.int32_id_eligible(max_known)
        if self.wide and not ops.wide_id_eligible(max_known):
            raise ValueError(
                "device engine ids exceed the wide-id bound "
                f"(max id {max_known} > {ops.WIDE_ID_MAX}); "
                "use the staged pipeline"
            )
        self._jnp = jnp
        self.engine = engine
        self.backend = backend
        self.policy = engine.policy
        self.stats = engine.stats  # shared — trainer.engine.stats stays live
        self.capacity = engine.capacity
        self.num_pes = engine.num_pes
        self.max_capacity = engine.max_capacity
        self.feature_dim = engine.feature_dim
        self._node_weights = engine._node_weights
        if self.wide:
            ids_hi, ids_lo = ops.split_ids(engine.ids)
            self._ids = jnp.asarray(ids_lo)
            self._ids_hi = jnp.asarray(ids_hi)
        else:
            self._ids = jnp.asarray(engine.ids.astype(np.int32))
            self._ids_hi = None
        self._scores = jnp.asarray(engine.scores)
        self._valid = jnp.asarray(engine.valid)
        self._accessed = jnp.asarray(engine.accessed)
        self._in_cap = jnp.asarray(engine.in_capacity)
        # Weights ride on device only when the policy reads them; with
        # use_weights=False the staged weights array is dead state.
        self._weights = (
            jnp.asarray(engine.weights) if self.policy.use_weights else None
        )
        self._weights0 = engine.weights.copy()
        self.payload = (
            jnp.asarray(engine.payload.reshape(-1, engine.feature_dim))
            if engine.payload is not None
            else None
        )
        P = self.num_pes
        self.last_placed = [np.array([], dtype=np.int64) for _ in range(P)]
        self.last_slots = [np.array([], dtype=np.int64) for _ in range(P)]
        self.last_hit_slots = [np.array([], dtype=np.int64) for _ in range(P)]

        # --- single-launch frontier path (fused_step_raw) -------------- #
        # part_of rides on device so dedup + remoteness run in-launch;
        # node degree weights likewise when the policy scores with them.
        self._part_of_dev = (
            jnp.asarray(np.asarray(part_of).astype(np.int32))
            if part_of is not None
            else None
        )
        self._node_w_dev = (
            jnp.asarray(self._node_weights.astype(np.float32))
            if (self.policy.use_weights and self._node_weights is not None)
            else None
        )
        self._store = None  # FeatureStore for the in-launch payload scatter
        # Two-deep candidate rotation: launch t replaces with the misses
        # launch t-2 compacted on device (prime probes only, so the
        # admission stream lags the probe stream by exactly one step —
        # the same rotation FusedFetchStage drives through host memory).
        self.cand_cap = 2 * self.max_capacity
        empty64 = np.array([], dtype=np.int64)
        self._cand_ready = jnp.full((P, 1), -1, dtype=jnp.int32)
        self._cand_ready_hi = (
            jnp.full((P, 1), -1, dtype=jnp.int32) if self.wide else None
        )
        self._cand_ready_ids = [empty64 for _ in range(P)]
        self._cand_pending = None
        self._cand_pending_hi = None
        self._cand_pending_ids = None
        # Host-boundary audit: one upload + one packed readback per step.
        self.transfers = {"h2d": 0, "h2d_bytes": 0, "d2h": 0, "d2h_bytes": 0}

    # ------------------------------------------------------------------ #
    def occupancy_of(self, n_valid: np.ndarray) -> np.ndarray:
        """`PrefetchEngine.occupancy` from a launch's n_valid output."""
        return np.where(
            self.capacity > 0, n_valid / np.maximum(self.capacity, 1), 0.0
        )

    def fused_step(
        self,
        queries: list[np.ndarray],
        candidates: list[np.ndarray],
        active_score: np.ndarray,
        do_replace: np.ndarray,
        active_probe: np.ndarray,
    ) -> FusedStepOut:
        """One fused launch: score (``end_round(active_score)``) →
        replace (``replace_round(candidates, do_replace)``) → probe
        (``lookup(queries, active_probe)``) — see the pipeline rotation
        in :class:`repro.runtime.stage.FusedFetchStage`. Ragged inputs
        are bucket-padded with -1 (candidate dedup happens in-kernel);
        per-PE stats / last_* bookkeeping is updated exactly as the
        staged engine does — all of it vectorized, no per-PE loop."""
        import jax

        P = self.num_pes
        do_rep = np.asarray(do_replace, dtype=bool)
        empty64 = np.array([], dtype=np.int64)
        # np.concatenate(dtype=...) converts + flattens each ragged item
        # at C speed — a per-item np.asarray listcomp costs ~0.4 ms/step
        # at P=256, a real slice of the fused step's budget.
        qlen = np.fromiter(map(len, queries), np.int64, count=P)
        cands = (
            list(candidates)
            if do_rep.all()
            else [candidates[p] if do_rep[p] else empty64 for p in range(P)]
        )
        clen = np.fromiter(map(len, cands), np.int64, count=P)
        allq = (
            np.concatenate(queries, dtype=np.int64, casting="unsafe")
            if qlen.sum()
            else empty64
        )
        allc = (
            np.concatenate(cands, dtype=np.int64, casting="unsafe")
            if clen.sum()
            else empty64
        )
        from ..kernels import ops

        max_in = max(
            int(allq.max()) if allq.size else -1,
            int(allc.max()) if allc.size else -1,
        )
        if self.wide:
            if not ops.wide_id_eligible(max_in):
                raise ValueError(
                    "device engine ids exceed the wide-id bound "
                    f"(max id {max_in} > {ops.WIDE_ID_MAX})"
                )
        elif not ops.int32_id_eligible(max_in):
            raise ValueError("device engine needs node ids < 2^31")
        M = _bucket(int(qlen.max(initial=0)))
        K = _bucket(int(clen.max(initial=0)))
        qmask = np.arange(M) < qlen[:, None]
        cmask = np.arange(K) < clen[:, None]
        q = np.full((P, M), -1, dtype=np.int32)
        c = np.full((P, K), -1, dtype=np.int32)
        q_hi = c_hi = None
        if self.wide:
            q_hi = np.full((P, M), -1, dtype=np.int32)
            c_hi = np.full((P, K), -1, dtype=np.int32)
            qh, ql = ops.split_ids(allq)
            ch, cl = ops.split_ids(allc)
            q[qmask] = ql
            q_hi[qmask] = qh
            c[cmask] = cl
            c_hi[cmask] = ch
        else:
            q[qmask] = allq
            c[cmask] = allc
        cw = None
        if self._weights is not None:
            cw = np.ones((P, K), dtype=np.float32)
            if self._node_weights is not None and allc.size:
                cw[cmask] = self._node_weights[allc - self.id_base]

        gates = (
            np.asarray(active_score, dtype=bool),
            np.asarray(do_replace, dtype=bool),
            np.asarray(active_probe, dtype=bool),
        )
        _launch_sp = tel.begin("device.launch", plane="device")
        if self.wide:
            (
                self._ids,
                self._ids_hi,
                self._scores,
                self._valid,
                self._accessed,
                w2,
                hit_d,
                hit_slot_d,
                placed_d,
                slot_pos_d,
                _n_placed,
                n_valid_d,
            ) = ops.fused_step_wide_batch(
                self._ids,
                self._ids_hi,
                self._scores,
                self._valid,
                self._accessed,
                self._in_cap,
                self._weights,
                q,
                q_hi,
                c,
                c_hi,
                cw,
                *gates,
                backend=self.backend,
                **self.policy.kernel_constants(),
            )
        else:
            (
                self._ids,
                self._scores,
                self._valid,
                self._accessed,
                w2,
                hit_d,
                hit_slot_d,
                placed_d,
                slot_pos_d,
                _n_placed,
                n_valid_d,
            ) = ops.fused_step_batch(
                self._ids,
                self._scores,
                self._valid,
                self._accessed,
                self._in_cap,
                self._weights,
                q,
                c,
                cw,
                *gates,
                backend=self.backend,
                **self.policy.kernel_constants(),
            )
        tel.end(_launch_sp)
        if w2 is not None:
            self._weights = w2
        # One packed int32 pull instead of five small device_gets — the
        # staged-path half of the single-transfer readback contract.
        with tel.span("device.readback", plane="device"):
            packed = jax.device_get(
                ops.pack_readback(
                    hit_d, hit_slot_d, placed_d, slot_pos_d, n_valid_d
                )
            )
        C = slot_pos_d.shape[1]
        hit = packed[:, :M] != 0
        hit_slot = packed[:, M : 2 * M]
        placed_m = packed[:, 2 * M : 2 * M + K] != 0
        slot_pos = packed[:, 2 * M + K : 2 * M + K + C]
        n_valid = packed[:, -1].astype(np.int64)
        h2d_bytes = (
            q.nbytes + c.nbytes + 3 * P
            + (cw.nbytes if cw is not None else 0)
            + (q_hi.nbytes + c_hi.nbytes if self.wide else 0)
        )
        self.transfers["h2d"] += (
            (5 if cw is None else 6) + (2 if self.wide else 0)
        )
        self.transfers["h2d_bytes"] += h2d_bytes
        self.transfers["d2h"] += 1
        self.transfers["d2h_bytes"] += packed.nbytes
        if tel.enabled():
            tel.count("device.h2d_bytes", h2d_bytes)
            tel.count("device.d2h_bytes", packed.nbytes)

        # --- probe bookkeeping (PrefetchEngine.lookup) ----------------- #
        lengths = np.where(np.asarray(active_probe, dtype=bool), qlen, 0)
        self.stats.lookups += lengths
        hits_per_pe = hit.sum(axis=1).astype(np.int64)
        self.stats.hits += hits_per_pe
        self.stats.misses += lengths - hits_per_pe
        flat_hit = hit[qmask]
        hit_masks = _split_by_counts(flat_hit, qlen)
        missed = _split_by_counts(allq[~flat_hit], qlen - hits_per_pe)
        hit_slots = _split_by_counts(
            hit_slot[qmask][flat_hit].astype(np.int64), hits_per_pe
        )
        self.last_hit_slots = list(hit_slots)

        # --- replacement bookkeeping (PrefetchEngine.replace_round) ---- #
        pm = placed_m & cmask
        n_per = pm.sum(axis=1).astype(np.int64)
        rounds = do_rep & (n_per > 0)
        self.stats.skipped_rounds += do_rep & (n_per == 0)
        self.stats.replaced_total += np.where(rounds, n_per, 0)
        self.stats.replacement_rounds += rounds
        replaced = np.where(rounds, n_per, 0)
        flat_pm = pm[cmask]
        self.last_placed = _split_by_counts(allc[flat_pm], n_per)
        # Placed candidates come out in candidate (= fresh-rank) order,
        # and the r-th placed candidate fills the slot with fill rank r:
        # a stable argsort of the per-slot fill ranks pairs them up —
        # cheaper than having the kernel reduce a second (P, K, C) max
        # for an explicit per-candidate slot output.
        order = np.argsort(slot_pos, axis=1, kind="stable").astype(np.int64)
        rank_mask = np.arange(slot_pos.shape[1]) < n_per[:, None]
        self.last_slots = _split_by_counts(order[rank_mask], n_per)
        return FusedStepOut(
            hit_masks=hit_masks,
            missed=missed,
            hits=hits_per_pe,
            hit_slots=hit_slots,
            replaced=replaced,
            placed=list(self.last_placed),
            placed_slots=list(self.last_slots),
            n_valid=n_valid,
        )

    # ------------------------------------------------------------------ #
    # single-launch frontier path
    # ------------------------------------------------------------------ #
    def attach_store(self, store) -> None:
        """Wire a :class:`repro.store.FeatureStore` into the launch: the
        kernel gathers admission rows from the store's flat device table
        (:meth:`FeatureStore.device_view`) straight into the payload."""
        self._store = store

    def fused_step_raw(
        self,
        touched: np.ndarray,
        active_score: np.ndarray,
        do_replace: np.ndarray,
        active_probe: np.ndarray,
        want: str = "full",
    ):
        """One single-launch device step over the *raw* sampled frontier:
        dedup → score → replace → probe → gather, one dispatch, one
        ``(P, Mt+1)`` upload (frontier + packed gate bits) and one packed
        readback — ≤2 host transfers per step.

        ``touched`` is the dense ``(P, Mt)`` frontier block straight from
        the sampler (unsorted, duplicated; -1 padding allowed).
        Replacement candidates are the misses the launch two steps back
        compacted on device (:attr:`_cand_ready` — the same two-deep
        pipeline rotation ``FusedFetchStage`` drives, minus the host
        hop). Bookkeeping and stats mirror :meth:`fused_step` exactly.

        ``want="counts"`` is the K-step readback cadence: the launch's
        host-facing block stays on device and only a ``(P, 4)``
        ``[n_remote, hits, n_place, n_valid]`` counter array is returned
        (as a *device* array — the caller stacks K of them and pulls
        once). No stats / last_* bookkeeping happens in counts mode; the
        cadence driver reconstructs stats from the counters.
        """
        import jax

        from ..kernels import ops

        P = self.num_pes
        if self._part_of_dev is None:
            raise ValueError(
                "fused_step_raw needs the partition map: construct the "
                "DeviceEngine with part_of=..."
            )
        touched = np.asarray(touched)
        if touched.ndim != 2 or touched.shape[0] != P:
            raise ValueError(
                f"touched must be (P, Mt) with P={P}, got {touched.shape}"
            )
        max_in = int(touched.max()) if touched.size else -1
        if self.wide:
            if not ops.wide_id_eligible(max_in):
                raise ValueError(
                    "device engine ids exceed the wide-id bound "
                    f"(max id {max_in} > {ops.WIDE_ID_MAX})"
                )
        elif not ops.int32_id_eligible(max_in):
            raise ValueError("device engine needs node ids < 2^31")
        if not self.wide:
            touched = touched.astype(np.int32, copy=False)
        if touched.shape[1] == 0:
            # Final drained launch: keep the (P, Mt>=1) shape the sort
            # prologue needs; an all(-1) row dedups to zero queries.
            touched = np.full((P, 1), -1, dtype=np.int32)
        do_rep = np.asarray(do_replace, dtype=bool)
        gates = (
            np.asarray(active_score, dtype=bool).astype(np.int32)
            | (do_rep.astype(np.int32) << 1)
            | (np.asarray(active_probe, dtype=bool).astype(np.int32) << 2)
        )
        if self.wide:
            # Wide ingest block: [lo | hi | gates], still one upload.
            t_hi, t_lo = ops.split_ids(touched)
            aug = np.concatenate([t_lo, t_hi, gates[:, None]], axis=1)
        else:
            aug = np.concatenate([touched, gates[:, None]], axis=1)
        self.transfers["h2d"] += 1
        self.transfers["h2d_bytes"] += aug.nbytes
        tel.count("device.h2d_bytes", aug.nbytes)

        table = loc = None
        if self._store is not None and self.payload is not None:
            table, loc = self._store.device_view()

        Kc = self._cand_ready.shape[1]
        _launch_sp = tel.begin("device.launch", plane="device")
        if self.wide:
            (
                self._ids,
                self._ids_hi,
                self._scores,
                self._valid,
                self._accessed,
                w2,
                payload2,
                cand_next,
                cand_next_hi,
                packed_d,
                counters_d,
            ) = ops.fused_frontier_step_wide_batch(
                self._ids,
                self._ids_hi,
                self._scores,
                self._valid,
                self._accessed,
                self._in_cap,
                self._weights,
                aug,
                self._part_of_dev,
                self._cand_ready,
                self._cand_ready_hi,
                self._node_w_dev,
                self.payload,
                table,
                loc,
                cand_cap=self.cand_cap,
                id_base=self.id_base,
                backend=self.backend,
                **self.policy.kernel_constants(),
            )
        else:
            cand_next_hi = None
            (
                self._ids,
                self._scores,
                self._valid,
                self._accessed,
                w2,
                payload2,
                cand_next,
                packed_d,
                counters_d,
            ) = ops.fused_frontier_step_batch(
                self._ids,
                self._scores,
                self._valid,
                self._accessed,
                self._in_cap,
                self._weights,
                aug,
                self._part_of_dev,
                self._cand_ready,
                self._node_w_dev,
                self.payload,
                table,
                loc,
                cand_cap=self.cand_cap,
                backend=self.backend,
                **self.policy.kernel_constants(),
            )
        tel.end(_launch_sp)
        if w2 is not None:
            self._weights = w2
        if payload2 is not None:
            self.payload = payload2

        if want == "counts":
            # Rotate the device candidate buffers and hand back only the
            # (P, 4) counters, still on device; the host mirrors are not
            # maintained (no per-step bookkeeping on the cadence path).
            if self._cand_pending is not None:
                self._cand_ready = self._cand_pending
                self._cand_ready_hi = self._cand_pending_hi
            self._cand_pending = cand_next
            self._cand_pending_hi = cand_next_hi
            return counters_d

        with tel.span("device.readback", plane="device"):
            packed = jax.device_get(packed_d)
        self.transfers["d2h"] += 1
        self.transfers["d2h_bytes"] += packed.nbytes
        tel.count("device.d2h_bytes", packed.nbytes)
        C = self.max_capacity
        if self.wide:
            # Wide packed: [sk_hi | sk_lo | code | placed | slot_pos | n].
            Mt = (aug.shape[1] - 1) // 2
            sk = ops.join_ids(packed[:, :Mt], packed[:, Mt : 2 * Mt])
            code = packed[:, 2 * Mt : 3 * Mt]
            placed_m = packed[:, 3 * Mt : 3 * Mt + Kc] != 0
            slot_pos = packed[:, 3 * Mt + Kc : 3 * Mt + Kc + C]
        else:
            Mt = aug.shape[1] - 1
            sk = packed[:, :Mt]
            code = packed[:, Mt : 2 * Mt]
            placed_m = packed[:, 2 * Mt : 2 * Mt + Kc] != 0
            slot_pos = packed[:, 2 * Mt + Kc : 2 * Mt + Kc + C]
        n_valid = packed[:, -1].astype(np.int64)

        # --- probe bookkeeping (lookup over the deduped remote sets) --- #
        remote_mask = code > 0
        n_remote = remote_mask.sum(axis=1).astype(np.int64)
        lengths = np.where(np.asarray(active_probe, dtype=bool), n_remote, 0)
        self.stats.lookups += lengths
        hits_per_pe = (code >= 2).sum(axis=1).astype(np.int64)
        self.stats.hits += hits_per_pe
        self.stats.misses += lengths - hits_per_pe
        flat_code = code[remote_mask]
        flat_hit = flat_code >= 2
        sk_remote = sk[remote_mask].astype(np.int64)
        remote = _split_by_counts(sk_remote, n_remote)
        hit_masks = _split_by_counts(flat_hit, n_remote)
        missed = _split_by_counts(sk_remote[~flat_hit], n_remote - hits_per_pe)
        hit_slots = _split_by_counts(
            (flat_code[flat_hit] - 2).astype(np.int64), hits_per_pe
        )
        self.last_hit_slots = list(hit_slots)

        # --- replacement bookkeeping (replace_round) ------------------- #
        clen = np.fromiter(map(len, self._cand_ready_ids), np.int64, count=P)
        cmask = np.arange(Kc) < clen[:, None]
        pm = placed_m & cmask
        n_per = pm.sum(axis=1).astype(np.int64)
        rounds = do_rep & (n_per > 0)
        self.stats.skipped_rounds += do_rep & (n_per == 0)
        self.stats.replaced_total += np.where(rounds, n_per, 0)
        self.stats.replacement_rounds += rounds
        replaced = np.where(rounds, n_per, 0)
        allc = (
            np.concatenate(self._cand_ready_ids)
            if clen.sum()
            else np.array([], dtype=np.int64)
        )
        self.last_placed = _split_by_counts(allc[pm[cmask]], n_per)
        order = np.argsort(slot_pos, axis=1, kind="stable").astype(np.int64)
        rank_mask = np.arange(slot_pos.shape[1]) < n_per[:, None]
        self.last_slots = _split_by_counts(order[rank_mask], n_per)

        # --- candidate rotation (device + host mirror) ----------------- #
        kc_next = cand_next.shape[1]
        if self._cand_pending is not None:
            self._cand_ready = self._cand_pending
            self._cand_ready_hi = self._cand_pending_hi
            self._cand_ready_ids = self._cand_pending_ids
        self._cand_pending = cand_next
        self._cand_pending_hi = cand_next_hi
        self._cand_pending_ids = [m[:kc_next] for m in missed]

        return FrontierStepOut(
            hit_masks=hit_masks,
            missed=missed,
            hits=hits_per_pe,
            hit_slots=hit_slots,
            replaced=replaced,
            placed=list(self.last_placed),
            placed_slots=list(self.last_slots),
            n_valid=n_valid,
            remote=remote,
            n_remote=n_remote,
        )

    # ------------------------------------------------------------------ #
    # feature payload (device-resident)
    # ------------------------------------------------------------------ #
    def pull_rows(self, slots_per_pe: list[np.ndarray]) -> list[np.ndarray]:
        """Payload rows at per-PE slots, one batched device gather
        (the probe-time hit-row capture of the store data plane)."""
        if self.payload is None:
            raise ValueError("engine has no payload (feature_dim=0)")
        jnp = self._jnp
        C = self.max_capacity
        lengths = [len(s) for s in slots_per_pe]
        if sum(lengths) == 0:
            empty = np.zeros((0, self.feature_dim), dtype=np.float32)
            return [empty.copy() for _ in slots_per_pe]
        flat = np.concatenate(
            [
                np.asarray(s, dtype=np.int64) + p * C
                for p, s in enumerate(slots_per_pe)
            ]
        )
        with tel.span("device.readback", plane="device"):
            rows = np.asarray(
                jnp.take(self.payload, jnp.asarray(flat), axis=0)
            )
        self.transfers["d2h"] += 1
        self.transfers["d2h_bytes"] += rows.nbytes
        tel.count("device.d2h_bytes", rows.nbytes)
        return [
            np.ascontiguousarray(b)
            for b in np.split(rows, np.cumsum(lengths)[:-1])
        ]

    def place_rows_batch(self, slots_per_pe, blocks, device_block=None):
        """Scatter admission rows into the device payload (one fused
        ``.at[].set``); ``device_block`` skips the host→device upload
        when the store gather already produced a device copy."""
        if self.payload is None:
            raise ValueError("engine has no payload (feature_dim=0)")
        jnp = self._jnp
        C = self.max_capacity
        idx, rows = [], []
        for p, slots in enumerate(slots_per_pe):
            if len(slots) != len(blocks[p]):
                raise ValueError(
                    f"PE {p}: {len(slots)} slots != {len(blocks[p])} rows"
                )
            if len(slots):
                idx.append(np.asarray(slots, dtype=np.int64) + p * C)
                rows.append(blocks[p])
        if not idx:
            return
        flat = np.concatenate(idx)
        if device_block is not None:
            data = device_block
        else:
            data = jnp.asarray(np.concatenate(rows, dtype=np.float32))
            self.transfers["h2d"] += 1
            self.transfers["h2d_bytes"] += sum(int(r.nbytes) for r in rows)
            tel.count(
                "device.h2d_bytes", sum(int(r.nbytes) for r in rows)
            )
        self.payload = self.payload.at[jnp.asarray(flat)].set(data)

    # ------------------------------------------------------------------ #
    def sync_to_engine(self) -> PrefetchEngine:
        """Write the device state back into the numpy twin (end of a
        device-mode run: snapshots, state-equality tests, reuse)."""
        eng = self.engine
        if self.wide:
            from ..kernels import ops

            eng.ids = ops.join_ids(
                np.asarray(self._ids_hi), np.asarray(self._ids)
            )
        else:
            eng.ids = np.asarray(self._ids).astype(np.int64)
        eng.scores = np.asarray(self._scores)
        eng.valid = np.asarray(self._valid)
        eng.accessed = np.asarray(self._accessed)
        if self._weights is not None:
            eng.weights = np.asarray(self._weights)
        elif self._node_weights is not None:
            # use_weights=False but node_weights given: the staged engine
            # still refreshes slot weights at placement (dead state for
            # scoring); reconstruct it instead of tracking it on device.
            eng.weights = np.where(
                eng.valid,
                self._node_weights[
                    np.maximum(eng.ids - self.id_base, 0)
                ].astype(np.float32),
                self._weights0,
            ).astype(np.float32)
        if self.payload is not None:
            eng.payload = np.asarray(self.payload).reshape(
                self.num_pes, self.max_capacity, self.feature_dim
            )
        eng.last_placed = [a.copy() for a in self.last_placed]
        eng.last_slots = [a.copy() for a in self.last_slots]
        eng.last_hit_slots = [a.copy() for a in self.last_hit_slots]
        return eng
