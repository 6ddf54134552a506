"""Public jit'd wrappers around the Pallas kernels.

Whether a kernel runs compiled or interpreted follows the platform
(:func:`interpret_mode`): Mosaic-compiled on a TPU, the Pallas
interpreter everywhere else. Every op has a pure-jnp oracle in
:mod:`repro.kernels.ref` and an allclose sweep in
``tests/test_kernels.py``. This module owns the int64 routing —
callers never need to check id ranges themselves. Kernel catalog:
``docs/KERNELS.md``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import functools

from . import ref
from .. import telemetry
from .frontier_unique import frontier_unique_batch as _frontier_unique_batch
from .frontier_unique import (
    frontier_unique_batch_wide as _frontier_unique_batch_wide,
)
from .fused_step import fused_frontier_step_pallas as _fused_frontier_step_pallas
from .fused_step import (
    fused_frontier_step_wide_pallas as _fused_frontier_step_wide_pallas,
)
from .fused_step import fused_step_pallas as _fused_step_pallas
from .fused_step import fused_step_wide_pallas as _fused_step_wide_pallas
from .gather_mean import gather_mean as _gather_mean
from .gather_rows import gather_rows as _gather_rows
from .gather_rows import gather_rows_batch as _gather_rows_batch
from .mla_decode import mla_flash_decode as _mla_flash_decode
from .score_update import score_policy_update_batch as _score_policy_update_batch
from .score_update import score_update as _score_update
from .score_update import score_update_batch as _score_update_batch
from .segment_sum import segment_sum_equal as _segment_sum_equal

__all__ = [
    "interpret_mode",
    "gather_rows",
    "gather_rows_batch",
    "gather_mean",
    "segment_sum_equal",
    "score_update",
    "score_update_batch",
    "score_policy_update_batch",
    "frontier_unique_batch",
    "fused_step_batch",
    "fused_step_wide_batch",
    "fused_frontier_step_batch",
    "fused_frontier_step_wide_batch",
    "pack_readback",
    "mla_flash_decode",
    "ref",
    "INT32_SENTINEL",
    "INT32_ID_MAX",
    "WIDE_ID_MAX",
    "int32_id_eligible",
    "wide_id_eligible",
    "split_ids",
    "join_ids",
]

#: The device kernels' padding sentinel (``frontier_pack``'s miss
#: compaction sorts empty positions to ``int32.max``). A *legitimate* id
#: equal to the sentinel would alias empty slots, so the narrow-id
#: eligibility bound strictly excludes it.
INT32_SENTINEL = int(np.iinfo(np.int32).max)

#: Largest node id the narrow (single-word int32) device path may carry:
#: ``2**31 - 2`` — one below ``INT32_SENTINEL``, see above.
INT32_ID_MAX = INT32_SENTINEL - 1

#: Largest node id the wide (two-word ``(hi, lo)``) device path may
#: carry: ``hi`` must stay below ``INT32_SENTINEL`` so the wide sentinel
#: pair ``(int32.max, int32.max)`` never aliases a real id, and
#: ``lo < 2**WIDE_SHIFT`` by construction.
WIDE_ID_MAX = (INT32_ID_MAX << ref.WIDE_SHIFT) | ref.WIDE_MASK


def int32_id_eligible(max_id) -> bool:
    """True when ids up to ``max_id`` fit the narrow int32 device path.

    The single eligibility predicate shared by every guard (dispatchers,
    ``DeviceEngine``, the driver's auto-upgrade, ``FeatureStore``) — the
    bound is ``max_id <= 2**31 - 2``, *strictly excluding* the
    ``int32.max`` padding sentinel."""
    return int(max_id) <= INT32_ID_MAX


def wide_id_eligible(max_id) -> bool:
    """True when ids up to ``max_id`` fit the two-word wide device path
    (``max_id <= WIDE_ID_MAX``, about 2^61)."""
    return int(max_id) <= WIDE_ID_MAX


def split_ids(ids):
    """Split an int64 id array into ``(hi, lo)`` int32 word planes.

    Non-negative ids split base-``2**WIDE_SHIFT`` (``hi = id >> 30``,
    ``lo = id & (2**30 - 1)``); negative sentinels (-1 empty, -2 masked)
    map to the equal pair ``(v, v)`` so pair equality is id equality and
    ``hi >= 0`` is validity. Numeric order of non-negative ids equals
    lexicographic ``(hi, lo)`` order — row-sorted int64 keys stay sorted
    plane-wise."""
    ids = np.asarray(ids, dtype=np.int64)
    neg = ids < 0
    v32 = ids.astype(np.int32)  # only read where negative (small values)
    hi = np.where(neg, v32, (ids >> ref.WIDE_SHIFT).astype(np.int32))
    lo = np.where(neg, v32, (ids & ref.WIDE_MASK).astype(np.int32))
    return hi, lo


def join_ids(hi, lo):
    """Inverse of :func:`split_ids`: rebuild int64 ids on host
    (``hi < 0`` rows are sentinels and pass through as ``hi``)."""
    hi = np.asarray(hi)
    lo = np.asarray(lo)
    return np.where(
        hi < 0,
        hi.astype(np.int64),
        (hi.astype(np.int64) << ref.WIDE_SHIFT) | lo.astype(np.int64),
    )


def interpret_mode() -> bool:
    """True where Pallas kernels run in the interpreter: on every
    backend but the TPU, whose Mosaic compiler the kernels target. The
    one place the choice is made; no caller passes it."""
    return jax.default_backend() != "tpu"


def _fused_launch(backend, pallas_fn, oracle_fn):
    """The fused-launch callable for ``backend``, counted in telemetry by
    the path it runs: ``device.launch.compiled``, ``.interpreted`` (the
    Pallas kernel, per :func:`interpret_mode`) or ``.oracle`` (jnp)."""
    if backend != "pallas":
        telemetry.count("device.launch.oracle")
        return oracle_fn
    interpret = interpret_mode()
    telemetry.count(
        "device.launch.interpreted" if interpret else "device.launch.compiled"
    )
    return functools.partial(pallas_fn, interpret=interpret)


_FUSED_STATICS = (
    "increment",
    "decay",
    "threshold",
    "score_cap",
    "mode",
    "initial_score",
)

_fused_step_ref = functools.partial(
    jax.jit, static_argnames=_FUSED_STATICS
)(ref.fused_step)

_fused_step_wide_ref = functools.partial(
    jax.jit, static_argnames=_FUSED_STATICS
)(ref.fused_step_wide)

_FRONTIER_STATICS = _FUSED_STATICS + ("cand_cap",)

_fused_frontier_ref = functools.partial(
    jax.jit, static_argnames=_FRONTIER_STATICS
)(ref.fused_frontier_step)

_FRONTIER_WIDE_STATICS = _FRONTIER_STATICS + ("id_base",)

_fused_frontier_wide_ref = functools.partial(
    jax.jit, static_argnames=_FRONTIER_WIDE_STATICS
)(ref.fused_frontier_step_wide)


@telemetry.profiled("pack_readback")
@jax.jit
def pack_readback(hit, hit_slot, placed, slot_pos, n_valid):
    """Pack the staged fused-step launch's five host-facing outputs into
    one int32 block ``[hit | hit_slot | placed | slot_pos | n_valid]``
    of width ``2*M + K + C + 1`` — a single device→host transfer per
    step instead of five small pulls (the residual ~0.4 ms/step
    ``np.asarray`` tax flagged in ``runtime/engine.py``). The host
    slices by the widths it already knows."""
    return jnp.concatenate(
        [
            hit.astype(jnp.int32),
            hit_slot.astype(jnp.int32),
            placed.astype(jnp.int32),
            slot_pos.astype(jnp.int32),
            n_valid[:, None].astype(jnp.int32),
        ],
        axis=1,
    )


@telemetry.profiled("fused_step_batch")
def fused_step_batch(
    ids,
    scores,
    valid,
    accessed,
    in_capacity,
    weights,
    queries,
    cand,
    cand_weights,
    active_score,
    do_replace,
    active_probe,
    *,
    increment: float = 1.0,
    decay: float = 0.95,
    threshold: float = 0.95,
    score_cap: float = 4.0,
    mode: str = "accumulate",
    initial_score: float = 1.0,
    backend: str = "jnp",
):
    """Fused per-minibatch hot path: score -> replace -> probe, one launch.

    State is ``(P, C)`` (``ids`` int32, -1 = empty), ``queries`` is
    ``(P, M)`` and ``cand`` ``(P, K)`` (both -1-padded), the three gate
    vectors are ``(P,)`` bool. Returns ``(ids, scores, valid, accessed,
    weights, hit, hit_slot, cand_placed, slot_pos, n_placed, n_valid)``
    — the new device-resident buffer state plus the compact per-query /
    per-candidate / per-slot outputs the host needs (O(P*(M+K+C))
    transfer, never the feature payload). ``slot_pos`` carries the
    per-slot fill rank (argsort it on host to pair placed candidates,
    in candidate order, with the slots they filled).

    ``backend="jnp"`` (default) runs the jit'd oracle
    :func:`repro.kernels.ref.fused_step`; ``backend="pallas"`` runs the
    Pallas kernel (``kernels/fused_step.py``, interpreted off the TPU).
    Each launch is counted in telemetry by the path it took
    (``device.launch.compiled`` / ``.interpreted`` / ``.oracle``). The
    device math is int32: int64 inputs with ids beyond the
    narrow bound (:func:`int32_id_eligible`) are split into ``(hi, lo)``
    word planes and routed through the wide twin on *either* backend —
    same outputs either way, ``ids`` rejoined to int64 on host. Ground
    truth is the staged ``PrefetchEngine`` pipeline itself
    (``tests/test_fused_step.py``); catalog entry
    ``docs/KERNELS.md#fused_step``.
    """
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"backend must be 'jnp' or 'pallas', got {backend!r}")
    constants = dict(
        increment=float(increment),
        decay=float(decay),
        threshold=float(threshold),
        score_cap=float(score_cap),
        mode=mode,
        initial_score=float(initial_score),
    )
    needs_wide = False
    for arr in (ids, cand, queries):
        if getattr(arr, "dtype", None) == np.int64:
            vals = np.asarray(arr)
            if vals.size and not int32_id_eligible(vals.max()):
                needs_wide = True
                break
    if needs_wide:
        for arr in (ids, cand, queries):
            vals = np.asarray(arr)
            if vals.size and not wide_id_eligible(vals.max()):
                raise ValueError(
                    "node ids exceed the wide-id device bound "
                    f"(max {int(vals.max())} > {WIDE_ID_MAX})"
                )
        ids_hi, ids_lo = split_ids(np.asarray(ids))
        q_hi, q_lo = split_ids(np.asarray(queries))
        c_hi, c_lo = split_ids(np.asarray(cand))
        out = fused_step_wide_batch(
            ids_lo,
            ids_hi,
            scores,
            valid,
            accessed,
            in_capacity,
            weights,
            q_lo,
            q_hi,
            c_lo,
            c_hi,
            cand_weights,
            active_score,
            do_replace,
            active_probe,
            backend=backend,
            **constants,
        )
        ids2 = join_ids(np.asarray(out[1]), np.asarray(out[0]))
        return (ids2,) + tuple(out[2:])
    fn = _fused_launch(backend, _fused_step_pallas, _fused_step_ref)
    return fn(
        ids,
        scores,
        valid,
        accessed,
        in_capacity,
        weights,
        queries,
        cand,
        cand_weights,
        active_score,
        do_replace,
        active_probe,
        **constants,
    )


@telemetry.profiled("fused_step_wide_batch")
def fused_step_wide_batch(
    ids,
    ids_hi,
    scores,
    valid,
    accessed,
    in_capacity,
    weights,
    queries,
    queries_hi,
    cand,
    cand_hi,
    cand_weights,
    active_score,
    do_replace,
    active_probe,
    *,
    increment: float = 1.0,
    decay: float = 0.95,
    threshold: float = 0.95,
    score_cap: float = 4.0,
    mode: str = "accumulate",
    initial_score: float = 1.0,
    backend: str = "jnp",
):
    """Wide-id twin of :func:`fused_step_batch`: every id operand is an
    ``(hi, lo)`` int32 word-pair plane (:func:`split_ids`), covering
    64-bit id universes without leaving the device. Returns the
    12-tuple of :func:`repro.kernels.ref.fused_step_wide` — the narrow
    outputs with ``ids2_hi`` inserted after ``ids2``."""
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"backend must be 'jnp' or 'pallas', got {backend!r}")
    constants = dict(
        increment=float(increment),
        decay=float(decay),
        threshold=float(threshold),
        score_cap=float(score_cap),
        mode=mode,
        initial_score=float(initial_score),
    )
    fn = _fused_launch(
        backend, _fused_step_wide_pallas, _fused_step_wide_ref
    )
    return fn(
        ids,
        ids_hi,
        scores,
        valid,
        accessed,
        in_capacity,
        weights,
        queries,
        queries_hi,
        cand,
        cand_hi,
        cand_weights,
        active_score,
        do_replace,
        active_probe,
        **constants,
    )


@telemetry.profiled("fused_frontier_step_batch")
def fused_frontier_step_batch(
    ids,
    scores,
    valid,
    accessed,
    in_capacity,
    weights,
    touched_aug,
    part_of,
    cand,
    node_weights,
    payload,
    table,
    loc,
    *,
    cand_cap: int,
    increment: float = 1.0,
    decay: float = 0.95,
    threshold: float = 0.95,
    score_cap: float = 4.0,
    mode: str = "accumulate",
    initial_score: float = 1.0,
    backend: str = "jnp",
):
    """Single-launch device step: dedup → score → replace → probe →
    gather, one dispatch per minibatch.

    ``touched_aug`` is the raw ``(P, Mt + 1)`` frontier block (unsorted,
    duplicated) with the per-PE gate bits packed into its last column —
    the step's one host→device transfer. ``cand`` is the previous
    launch's on-device miss compaction; ``part_of`` / ``node_weights`` /
    ``payload`` / ``table`` / ``loc`` are persistent device arrays. All
    int arrays must already be int32 — the caller
    (:class:`repro.runtime.engine.DeviceEngine`) owns the int64 range
    guard up front, there is no per-step fallback to re-check.

    Returns ``(ids2, scores2, valid2, accessed3, weights2, payload2,
    cand_next, packed, counters)``; only ``packed`` (or, on the K-step
    readback cadence, ``counters``) ever crosses back to host.
    ``backend="jnp"`` runs the jit'd oracle
    :func:`repro.kernels.ref.fused_frontier_step`; ``backend="pallas"``
    the Pallas megakernel on every shape, zero-capacity buffers and the
    final launch's empty frontier included. Catalog entry
    ``docs/KERNELS.md#fused_step``.
    """
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"backend must be 'jnp' or 'pallas', got {backend!r}")
    constants = dict(
        cand_cap=int(cand_cap),
        increment=float(increment),
        decay=float(decay),
        threshold=float(threshold),
        score_cap=float(score_cap),
        mode=mode,
        initial_score=float(initial_score),
    )
    fn = _fused_launch(
        backend, _fused_frontier_step_pallas, _fused_frontier_ref
    )
    return fn(
        ids,
        scores,
        valid,
        accessed,
        in_capacity,
        weights,
        touched_aug,
        part_of,
        cand,
        node_weights,
        payload,
        table,
        loc,
        **constants,
    )


@telemetry.profiled("fused_frontier_step_wide_batch")
def fused_frontier_step_wide_batch(
    ids,
    ids_hi,
    scores,
    valid,
    accessed,
    in_capacity,
    weights,
    touched_aug,
    part_of,
    cand,
    cand_hi,
    node_weights,
    payload,
    table,
    loc,
    *,
    cand_cap: int,
    id_base: int = 0,
    increment: float = 1.0,
    decay: float = 0.95,
    threshold: float = 0.95,
    score_cap: float = 4.0,
    mode: str = "accumulate",
    initial_score: float = 1.0,
    backend: str = "jnp",
):
    """Wide-id twin of :func:`fused_frontier_step_batch`.

    ``touched_aug`` is the raw ``(P, 2*Mt + 1)`` ``[lo | hi | gates]``
    ingest block (still one host→device transfer per step); buffer /
    candidate ids ride as ``(hi, lo)`` planes; ``id_base`` is the
    graph's global-id offset for the local-indexed ``part_of`` /
    ``node_weights`` / ``loc`` gathers (static under jit — one
    compilation per graph). Returns the 11-tuple of
    :func:`repro.kernels.ref.fused_frontier_step_wide`; only ``packed``
    (width ``3*Mt + K + C + 1``) ever crosses back to host."""
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"backend must be 'jnp' or 'pallas', got {backend!r}")
    constants = dict(
        cand_cap=int(cand_cap),
        id_base=int(id_base),
        increment=float(increment),
        decay=float(decay),
        threshold=float(threshold),
        score_cap=float(score_cap),
        mode=mode,
        initial_score=float(initial_score),
    )
    fn = _fused_launch(
        backend, _fused_frontier_step_wide_pallas, _fused_frontier_wide_ref
    )
    return fn(
        ids,
        ids_hi,
        scores,
        valid,
        accessed,
        in_capacity,
        weights,
        touched_aug,
        part_of,
        cand,
        cand_hi,
        node_weights,
        payload,
        table,
        loc,
        **constants,
    )


@telemetry.profiled("frontier_unique_batch")
def frontier_unique_batch(sorted_keys, is_remote):
    """Fused frontier dedup; accepts int32 **or** int64 row-sorted keys.

    The narrow Pallas kernel runs in int32; keys beyond the narrow bound
    (:func:`int32_id_eligible`) are split into ``(hi, lo)`` word planes
    and routed through the wide Pallas twin
    (:func:`repro.kernels.frontier_unique.frontier_unique_batch_wide`)
    with **identical output dtypes** (bool masks, int32 counts), so
    downstream consumers — and the trace schema's id normalization —
    see one contract on every platform. (The pre-wide behaviour cast
    int64 keys blindly, which silently wrapped ids >= 2^31 on the
    kernel path; then a numpy fallback fixed the values but left the
    device.)
    """
    if getattr(sorted_keys, "dtype", None) != np.int32:
        # Only non-int32 inputs pay the range check (and, for numpy
        # callers, it is free of any device transfer; int32 jax arrays
        # go straight to the kernel).
        keys = np.asarray(sorted_keys)
        if keys.size and not int32_id_eligible(keys.max()):
            if not wide_id_eligible(keys.max()):
                raise ValueError(
                    "frontier keys exceed the wide-id device bound "
                    f"(max {int(keys.max())} > {WIDE_ID_MAX})"
                )
            hi, lo = split_ids(keys)
            # Numeric int64 order == lexicographic (hi, lo) order, so
            # the row-sorted invariant carries over plane-wise.
            return _frontier_unique_batch_wide(
                lo, hi, is_remote, interpret=interpret_mode()
            )
        sorted_keys = keys.astype(np.int32, copy=False)
    return _frontier_unique_batch(
        sorted_keys, is_remote, interpret=interpret_mode()
    )


@telemetry.profiled("gather_rows")
def gather_rows(table, indices):
    return _gather_rows(table, indices, interpret=interpret_mode())


@telemetry.profiled("gather_mean")
def gather_mean(table, indices):
    return _gather_mean(table, indices, interpret=interpret_mode())


@telemetry.profiled("segment_sum_equal")
def segment_sum_equal(data, k: int):
    return _segment_sum_equal(data, k, interpret=interpret_mode())


@telemetry.profiled("score_update")
def score_update(scores, accessed):
    return _score_update(scores, accessed, interpret=interpret_mode())


@telemetry.profiled("gather_rows_batch")
def gather_rows_batch(tables, indices):
    return _gather_rows_batch(tables, indices, interpret=interpret_mode())


@telemetry.profiled("score_update_batch")
def score_update_batch(scores, accessed):
    return _score_update_batch(scores, accessed, interpret=interpret_mode())


@telemetry.profiled("score_policy_update_batch")
def score_policy_update_batch(
    scores,
    accessed,
    weights=None,
    *,
    increment: float = 1.0,
    decay: float = 0.95,
    threshold: float = 0.95,
    mode: str = "accumulate",
    score_cap: float = 4.0,
):
    return _score_policy_update_batch(
        scores,
        accessed,
        weights,
        increment=increment,
        decay=decay,
        threshold=threshold,
        mode=mode,
        score_cap=score_cap,
        interpret=interpret_mode(),
    )


@telemetry.profiled("mla_flash_decode")
def mla_flash_decode(q_lat, q_rope, cache_c, cache_kr, pos, *, scale=None):
    return _mla_flash_decode(
        q_lat, q_rope, cache_c, cache_kr, pos, scale=scale,
        interpret=interpret_mode(),
    )
