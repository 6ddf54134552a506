"""Partition-major sharded feature store.

Layout: node features live in one partition-major padded table. With K
partitions of at most ``N_max`` nodes, node ``v`` homed on partition
``k`` at local rank ``r`` (rank = position within the home partition's
id-sorted node list) sits at flat row ``loc[v] = k * N_max + r`` of a
``(K * N_max, F)`` float32 table — equivalently slice ``k`` of the
stacked ``(K, N_max, F)`` shard view. A gather of any id set is then a
single vectorized row gather, whatever mix of home partitions the ids
span; the per-home routing that a DistDGL KVStore pull performs
(one RPC per home partition) is only materialized on the kernel path,
where :func:`repro.kernels.ops.gather_rows_batch` consumes exactly that
``(K, M_max)`` per-shard request matrix.

Backends:

* ``"numpy"`` — host-local fallback; the flat table is a numpy array and
  gathers are fancy indexing. This is the bit-exactness reference (rows
  are verbatim copies of ``Graph.features`` rows) and the default on a
  single-device host.
* ``"jax"`` — the flat table is a jax device array, sharded across this
  process's devices over the 1-D :data:`repro.models.sharding.DATA_AXIS`
  mesh when the row count divides (the :func:`repro.models.sharding.guard`
  rule — otherwise replicated). Gathers are ``jnp.take``; values are
  bit-identical to the numpy path (a gather copies rows, it never
  rounds).
* ``backend="auto"`` picks ``"jax"`` on a multi-device host and
  ``"numpy"`` otherwise.

``use_kernel=True`` additionally routes gathers through the Pallas
batch-gather kernel: requests are bucketed by home partition into a
dense ``(K, M_max)`` local-row matrix and served by one
``gather_rows_batch`` call (interpret mode on CPU).
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from .. import telemetry as tel


@dataclass
class StoreGather:
    """Result of one batched (multi-PE) store gather."""

    blocks: list[np.ndarray]  # per-request (m_i, F) float32 feature blocks
    nbytes: int               # bytes actually moved out of the store
    seconds: float            # wall-clock time of the gather
    #: Concatenated block as a jax device array (``gather_batch(...,
    #: device=True)``) — the fused device hot path scatters it straight
    #: into the device-resident engine payload without a second
    #: host→device upload. None on host-only gathers.
    device_block: object = None


class FeatureStore:
    """Per-partition feature shards behind a single gather interface.

    Parameters
    ----------
    features:
        ``(N, F)`` feature matrix (any float dtype; stored as float32,
        matching :class:`repro.graph.generate.Graph` features).
    part_of:
        ``(N,)`` home partition per node.
    num_parts:
        Partition count ``K``; inferred from ``part_of`` when omitted.
    backend:
        ``"numpy"`` | ``"jax"`` | ``"auto"`` (see module docstring).
    use_kernel:
        Serve gathers through ``repro.kernels.ops.gather_rows_batch``
        (per-home routing into the stacked shard view).
    id_base:
        Global-id offset of the graph: gather/lookup ids are global
        (``id_base`` + local row), rebased to local before indexing
        ``loc``. The device view stays local-indexed — wide-id kernels
        rebase inside the launch with the same static ``id_base``.
    """

    def __init__(
        self,
        features: np.ndarray,
        part_of: np.ndarray,
        num_parts: int | None = None,
        backend: str = "auto",
        use_kernel: bool = False,
        id_base: int = 0,
    ):
        features = np.asarray(features, dtype=np.float32)
        if features.ndim != 2:
            raise ValueError(f"features must be (N, F), got {features.shape}")
        part_of = np.asarray(part_of, dtype=np.int64)
        if part_of.shape != (features.shape[0],):
            raise ValueError(
                f"part_of shape {part_of.shape} != ({features.shape[0]},)"
            )
        if part_of.size and part_of.min() < 0:
            raise ValueError("part_of must be non-negative")
        K = int(num_parts) if num_parts is not None else int(part_of.max(initial=0)) + 1
        if part_of.size and int(part_of.max()) >= K:
            raise ValueError("part_of references a partition >= num_parts")
        self.num_parts = K
        self.num_nodes, self.feature_dim = features.shape
        self.id_base = int(id_base)
        counts = np.bincount(part_of, minlength=K)
        self.shard_sizes = counts.astype(np.int64)
        self.n_max = int(counts.max(initial=0)) or 1

        # loc[v] = home * N_max + local_rank; ranks follow ascending node
        # id within each home partition (stable, derivable on any host).
        order = np.argsort(part_of, kind="stable")  # groups homes, keeps id order
        rank = np.empty(self.num_nodes, dtype=np.int64)
        rank[order] = np.arange(self.num_nodes, dtype=np.int64) - np.repeat(
            np.concatenate([[0], np.cumsum(counts)[:-1]]), counts
        )
        self._loc = part_of * self.n_max + rank

        flat = np.zeros((K * self.n_max, self.feature_dim), dtype=np.float32)
        flat[self._loc] = features
        self._flat = flat

        if backend == "auto":
            import jax

            backend = "jax" if len(jax.devices()) > 1 else "numpy"
        if backend not in ("numpy", "jax"):
            raise ValueError(f"unknown backend {backend!r}")
        self.backend = backend
        self.use_kernel = bool(use_kernel)
        self._dev = None          # jax flat table (backend="jax")
        self._tables = None       # jax (K, N_max, F) shard view (kernel path)
        self._dev_view = None     # (flat table, int32 loc) for the megakernel
        if backend == "jax":
            self._dev = self._device_table()

    # ------------------------------------------------------------------ #
    @classmethod
    def for_partitions(cls, parts, **kwargs) -> "FeatureStore":
        """Build from a :class:`repro.graph.partition.Partitioned`."""
        kwargs.setdefault("id_base", int(parts.graph.id_base))
        return cls(
            parts.graph.features, parts.part_of, parts.num_parts, **kwargs
        )

    # ------------------------------------------------------------------ #
    @property
    def nbytes(self) -> int:
        return self._flat.nbytes

    @property
    def shards(self) -> np.ndarray:
        """Stacked ``(K, N_max, F)`` shard view of the flat table."""
        return self._flat.reshape(self.num_parts, self.n_max, self.feature_dim)

    def home_of(self, ids) -> np.ndarray:
        local = np.asarray(ids, dtype=np.int64) - self.id_base
        return self._loc[local] // self.n_max

    def _device_table(self):
        """Flat table as a jax array, row-sharded over the data mesh
        when the divisibility guard admits it (replicated otherwise)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        from ..models.sharding import DATA_AXIS, guard

        mesh = Mesh(np.array(jax.devices()), (DATA_AXIS,))
        spec = guard(mesh, PartitionSpec(DATA_AXIS, None), self._flat.shape)
        return jax.device_put(
            jnp.asarray(self._flat), NamedSharding(mesh, spec)
        )

    def device_view(self):
        """``(table, loc)`` device pair for the single-launch hot path.

        ``table`` is the flat ``(K * N_max, F)`` float32 store as a jax
        array and ``loc`` the int32 node→row map; the fused frontier
        kernel gathers admission rows from these *inside* the launch, so
        the feature payload never crosses the host boundary. Cached
        until :meth:`poke` invalidates it. Requires the flat row count
        to be int32-addressable — the same bound the device engine
        already enforces on node ids.

        The view lives on the default device, beside the engine's state,
        even when the store's own table is sharded: a launch with one
        sharded operand would be partitioned over every device, and
        Mosaic kernels cannot be partitioned automatically."""
        if self._dev_view is None:
            import jax.numpy as jnp

            from ..kernels import ops

            if not ops.int32_id_eligible(self._flat.shape[0] - 1):
                raise ValueError(
                    "feature store flat table has >= 2^31 rows; "
                    "device view indexes rows as int32"
                )
            self._dev_view = (
                jnp.asarray(self._flat),
                jnp.asarray(self._loc.astype(np.int32)),
            )
        return self._dev_view

    @property
    def has_device_view(self) -> bool:
        """Whether :meth:`device_view` holds its pair, so that asking for
        it uploads nothing."""
        return self._dev_view is not None

    # ------------------------------------------------------------------ #
    def _rows_of(self, ids: np.ndarray) -> np.ndarray:
        flat = ids.reshape(-1).astype(np.int64, copy=False)
        if self.id_base:
            flat = flat - np.int64(self.id_base)
        if flat.size:
            lo, hi = int(flat.min()), int(flat.max())
            if lo < 0 or hi >= self.num_nodes:
                raise IndexError(
                    f"node id out of range "
                    f"[{self.id_base}, {self.id_base + self.num_nodes}): "
                    f"min {lo + self.id_base}, max {hi + self.id_base}"
                )
        return self._loc[flat]

    def _gather_rows(self, rows: np.ndarray) -> np.ndarray:
        if self.use_kernel:
            return self._gather_rows_kernel(rows)
        if self.backend == "jax":
            import jax.numpy as jnp

            return np.asarray(jnp.take(self._dev, jnp.asarray(rows), axis=0))
        return self._flat[rows]

    def _gather_rows_kernel(self, rows: np.ndarray) -> np.ndarray:
        """Per-home routing through the Pallas batch gather: bucket the
        request by home partition into a dense (K, M_max) local-row
        matrix — the DistDGL KVStore pull shape — and serve every shard
        in one ``gather_rows_batch`` call."""
        from ..kernels import ops

        K, F = self.num_parts, self.feature_dim
        M = rows.shape[0]
        if M == 0:
            return np.zeros((0, F), dtype=np.float32)
        home = rows // self.n_max
        local = rows - home * self.n_max
        order = np.argsort(home, kind="stable")
        counts = np.bincount(home, minlength=K)
        m_max = max(int(counts.max(initial=0)), 1)
        idx = np.zeros((K, m_max), dtype=np.int32)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        sorted_local = local[order]
        for k in range(K):
            idx[k, : counts[k]] = sorted_local[starts[k] : starts[k] + counts[k]]
        if self._tables is None:
            import jax.numpy as jnp

            self._tables = jnp.asarray(self.shards)
        out = np.asarray(ops.gather_rows_batch(self._tables, idx))
        gathered = np.concatenate([out[k, : counts[k]] for k in range(K)])
        result = np.empty((M, F), dtype=np.float32)
        result[order] = gathered
        return result

    # ------------------------------------------------------------------ #
    def gather(self, ids) -> np.ndarray:
        """Feature rows of ``ids`` — any shape, any int dtype; returns
        ``ids.shape + (F,)`` float32, bit-identical to
        ``graph.features[ids]``."""
        arr = np.asarray(ids)
        rows = self._rows_of(arr)
        block = self._gather_rows(rows)
        return block.reshape(arr.shape + (self.feature_dim,))

    def gather_batch(self, id_lists, device: bool = False) -> StoreGather:
        """One timed gather for a whole cluster's per-PE request lists.

        The P ragged requests are served by a single concatenated row
        gather and split back — this is the batched data path
        ``FetchStage.commit`` drives, and what the store microbenchmark
        races against a per-PE, per-home python pull loop.

        ``device=True`` additionally returns the concatenated block as a
        jax device array (``StoreGather.device_block``): the fused
        device hot path (:class:`repro.runtime.stage.FusedFetchStage`)
        scatters admission rows into the device-resident engine payload
        without re-uploading the block it just pulled. The numpy blocks
        (and every exact stream derived from them) are unchanged.
        """
        sp = tel.span("store.gather", plane="store")
        sp.__enter__()
        t0 = time.perf_counter()
        lengths = [len(x) for x in id_lists]
        if sum(lengths):
            ids = np.concatenate(
                [np.asarray(x, dtype=np.int64).reshape(-1) for x in id_lists]
            )
        else:
            ids = np.array([], dtype=np.int64)
        block = self._gather_rows(self._rows_of(ids))
        blocks = [
            np.ascontiguousarray(b)
            for b in np.split(block, np.cumsum(lengths)[:-1])
        ]
        device_block = None
        if device:
            import jax.numpy as jnp

            device_block = jnp.asarray(block)
        seconds = time.perf_counter() - t0
        sp.nbytes = int(block.nbytes)
        sp.__exit__(None, None, None)
        if tel.enabled():
            tel.count("store.bytes", block.nbytes)
            tel.count("store.gathers", 1)
            tel.count(
                "store.rows",
                np.asarray(lengths, dtype=np.float64),
            )
        return StoreGather(
            blocks=blocks,
            nbytes=int(block.nbytes),
            seconds=seconds,
            device_block=device_block,
        )

    # ------------------------------------------------------------------ #
    def poke(self, node_id: int, delta: float = 1.0) -> None:
        """Fault injection: corrupt one shard row in place (the golden
        drift negative test — a poked store must surface in the trace's
        ``feat_sums`` stream at the first step that fetches the node)."""
        row = self._loc[int(node_id) - self.id_base]
        self._flat[row] += np.float32(delta)
        self._tables = None
        self._dev_view = None
        if self.backend == "jax":
            self._dev = self._device_table()
