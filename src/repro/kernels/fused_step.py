"""Pallas TPU kernel: the fused per-minibatch hot path (megakernel).

One launch per training step keeps the entire `(P, C)` cluster buffer
state device-resident and performs, in the staged pipeline's exact
operation order, three rounds that previously round-tripped through
numpy between kernels:

1. **score** — close step t's sampling round (``PrefetchEngine.end_round``):
   the policy-zoo update (accumulate / reset / capped, optional degree
   weights) on valid slots of scoring-active PEs, access marks cleared.
2. **replace** — step t's replacement round (``PrefetchEngine.replace_round``):
   fresh candidates (not already resident) fill free slots first, then
   stale slots (post-score ``score < threshold``), both in ascending
   slot order, in candidate order, at ``initial_score``.
3. **probe** — step t+1's membership lookup (``PrefetchEngine.lookup``):
   per-query hit mask + hit slot, hit slots marked accessed for the
   *next* scoring round.

The probe of step t+1 rides in step t's launch because the controller
decision for a step is computed on host between probes — see the
pipeline rotation in :class:`repro.runtime.stage.FusedFetchStage`.

Grid: ``(P,)`` — one program per trainer PE; each program owns
lane-padded ``(1, C)`` state rows plus ``(1, M)`` query and ``(1, K)``
candidate rows (every ``(P, W)`` operand rides as ``(P, 1, W)`` with a
``(None, 1, W)`` block, which is what Mosaic's block-shape rule admits),
and builds dense ``(K, C)`` / ``(K, K)`` / ``(M, C)`` comparison tiles in
VMEM (prefix-count slot ranking + one-hot candidate→slot matching — no
ragged Python loop; the host pairs placed candidates with slots from
the returned per-slot fill ranks). Rows turn into columns through an
aligned ``(128, W)`` transpose and prefix counts are a scan of lane
rotations, since Mosaic lowers neither lane→sublane reshapes nor
``cumsum``. Those dense tiles bound the launch: see
``docs/KERNELS.md#fused_step`` for the VMEM ceiling.

Ids are int32 (-1 = empty/padding); the public dispatcher
:func:`repro.kernels.ops.fused_step_batch` guards the int64→int32 range
and routes wider ids through the two-word twin. Parity:
``tests/test_fused_step.py`` (staged ``PrefetchEngine`` ground truth +
hypothesis suite); v5e compile checks: ``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import scoring
from . import ref as _ref

LANES = 128

#: Scoped-VMEM budget of one fused launch. The dense comparison tiles
#: outgrow Mosaic's 16 MB default (16.4 MB at P=4, C=1294, Mt=8352); a
#: v5e core has 128 MiB of VMEM, and this leaves headroom below it.
VMEM_LIMIT_BYTES = 100 * 2**20


def _col(x):
    """``(1, W)`` row → ``(W, 1)`` column via an aligned ``(128, W)``
    transpose (W is lane-padded to a multiple of 128)."""
    return jnp.broadcast_to(x, (LANES, x.shape[1])).T[:, :1]


def _row(x):
    """``(W, 1)`` column → ``(1, W)`` row; inverse of :func:`_col`."""
    return jnp.broadcast_to(x, (x.shape[0], LANES)).T[:1, :]


def _prefix_count(mask):
    """Inclusive prefix sum of a ``(1, W)`` bool row as int32: a
    Hillis–Steele scan over lane rotations (``log2 W`` steps)."""
    x = mask.astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = 1
    while shift < x.shape[1]:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, 1), 0)
        shift *= 2
    return x


def _count(mask):
    """Number of set lanes of a ``(1, W)`` bool row, as ``(1, 1)``."""
    return jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)


def _fused_body(
    ids,
    s,
    v,
    a,
    incap,
    w,
    q,
    cand,
    cand_w,
    active_score,
    do_replace,
    active_probe,
    ids_hi=None,
    q_hi=None,
    cand_hi=None,
    *,
    increment,
    decay,
    threshold,
    score_cap,
    mode,
    initial_score,
):
    """Single-PE fused round over ``(1, C)`` / ``(1, M)`` / ``(1, K)``
    rows; the three gates are ``(1, 1)`` bools.

    With the optional ``*_hi`` planes present (the two-word id
    encoding — ``kernels/ref.py`` ``WIDE_SHIFT``), every id compare is
    a pair equality over both int32 planes, candidate/query validity is
    ``hi >= 0``, and the returned ``ids2_hi`` carries the new hi plane
    (None on the narrow path)."""
    wide = ids_hi is not None
    C = ids.shape[1]
    K = cand.shape[1]
    M = q.shape[1]

    # -- 1. scoring round (end_round) ---------------------------------- #
    gain = jnp.float32(increment)
    if w is not None:
        gain = gain * w
    if mode == "accumulate":
        touched = s + gain
    elif mode == "reset":
        touched = gain + jnp.zeros_like(s)
    else:  # capped
        touched = jnp.minimum(s + gain, jnp.float32(score_cap))
    new_s = jnp.where(a, touched, s * jnp.float32(decay))
    s1 = jnp.where(jnp.logical_and(active_score, v), new_s, s)
    acc1 = jnp.logical_and(a, jnp.logical_not(active_score))

    # -- 2. replacement round (replace_round) -------------------------- #
    # Candidates run down the sublanes, (K, 1), against (1, C) slots.
    cand_t = _col(cand)
    cand_hi_t = _col(cand_hi) if wide else None
    eq_m = cand_t == ids
    if wide:
        eq_m = jnp.logical_and(eq_m, cand_hi_t == ids_hi)
    member = jnp.any(jnp.logical_and(eq_m, v), axis=1, keepdims=True)
    # First-occurrence dedup (`_unique_preserve_order` in-kernel): a
    # candidate equal to an earlier position is never fresh.
    eq_d = cand_t == cand
    if wide:
        eq_d = jnp.logical_and(eq_d, cand_hi_t == cand_hi)
    dup = jnp.any(
        jnp.logical_and(
            eq_d,
            jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
            < jax.lax.broadcasted_iota(jnp.int32, (K, K), 0),
        ),
        axis=1,
        keepdims=True,
    )
    cand_ok = (cand_hi_t >= 0) if wide else (cand_t >= 0)
    fresh_t = jnp.logical_and(
        jnp.logical_and(cand_ok, jnp.logical_not(member)),
        jnp.logical_and(jnp.logical_not(dup), do_replace),
    )
    fresh = _row(fresh_t.astype(jnp.int32)) != 0
    free = jnp.logical_and(jnp.logical_not(v), incap)
    stale = jnp.logical_and(v, s1 < jnp.float32(threshold))
    n_free = _count(free)
    free_rank = _prefix_count(free) - 1
    stale_rank = n_free + _prefix_count(stale) - 1
    big = jnp.int32(C + K + 1)
    slot_pos = jnp.where(free, free_rank, jnp.where(stale, stale_rank, big))
    fresh_rank = jnp.where(fresh, _prefix_count(fresh) - 1, big + 1)
    n_place = jnp.where(
        do_replace,
        jnp.minimum(n_free + _count(stale), _count(fresh)),
        0,
    )
    placed = jnp.logical_and(fresh, fresh_rank < n_place)
    filled = slot_pos < n_place
    match = jnp.logical_and(
        _col(placed.astype(jnp.int32)) != 0, _col(fresh_rank) == slot_pos
    )
    new_id = jnp.sum(jnp.where(match, cand_t, 0), axis=0, keepdims=True)
    ids2 = jnp.where(filled, new_id, ids)
    if wide:
        new_id_hi = jnp.sum(
            jnp.where(match, cand_hi_t, 0), axis=0, keepdims=True
        )
        ids2_hi = jnp.where(filled, new_id_hi, ids_hi)
    else:
        ids2_hi = None
    s2 = jnp.where(filled, jnp.float32(initial_score), s1)
    v2 = jnp.logical_or(v, filled)
    if w is not None:
        new_w = jnp.sum(
            jnp.where(match, _col(cand_w), jnp.float32(0.0)),
            axis=0,
            keepdims=True,
        )
        w2 = jnp.where(filled, new_w, w)
    else:
        w2 = None
    acc2 = jnp.logical_and(acc1, jnp.logical_not(filled))

    # -- 3. membership probe of the next round (lookup) ---------------- #
    q_t = _col(q)
    eq_q = q_t == ids2
    if wide:
        q_hi_t = _col(q_hi)
        eq_q = jnp.logical_and(eq_q, q_hi_t == ids2_hi)
    q_ok = (q_hi_t >= 0) if wide else (q_t >= 0)
    qhit = jnp.logical_and(
        jnp.logical_and(eq_q, v2),
        jnp.logical_and(q_ok, active_probe),
    )
    hit_t = jnp.any(qhit, axis=1, keepdims=True)
    slot_iota_mc = jax.lax.broadcasted_iota(jnp.int32, (M, C), 1)
    hit_slot_t = jnp.where(
        hit_t,
        jnp.sum(jnp.where(qhit, slot_iota_mc, 0), axis=1, keepdims=True),
        -1,
    )
    hit = _row(hit_t.astype(jnp.int32)) != 0
    hit_slot = _row(hit_slot_t)
    acc3 = jnp.logical_or(acc2, jnp.any(qhit, axis=0, keepdims=True))
    return ids2, ids2_hi, s2, v2, acc3, w2, hit, hit_slot, placed, slot_pos




def _padded(n):
    """Lane-padded width of an ``n``-wide row: a multiple of 128, and at
    least one lane tile, so an empty row still gives the grid a block."""
    return max(LANES, -(-n // LANES) * LANES)


def _pad_lanes(x, constant):
    pad = _padded(x.shape[1]) - x.shape[1]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad)), constant_values=constant)


def _gate_rows(active_score, do_replace, active_probe):
    """The three ``(P,)`` gate vectors as one lane-padded ``(P, 128)``
    int32 operand (lanes 0..2)."""
    gates = jnp.stack(
        [
            active_score.astype(jnp.int32),
            do_replace.astype(jnp.int32),
            active_probe.astype(jnp.int32),
        ],
        axis=1,
    )
    return _pad_lanes(gates, 0)


def _read_gates(gates):
    """``(active_score, do_replace, active_probe)`` as ``(1, 1)`` bools."""
    return gates[:, 0:1] != 0, gates[:, 1:2] != 0, gates[:, 2:3] != 0


def _constants(increment, decay, threshold, score_cap, mode, initial_score):
    return dict(
        increment=float(increment),
        decay=float(decay),
        threshold=float(threshold),
        score_cap=float(score_cap),
        mode=mode,
        initial_score=float(initial_score),
    )


def _make_fused_kernel(constants, weighted, wide):
    """Kernel factory for the fused score→replace→probe launch.

    The operand list is computed from the (weighted, wide) configuration
    rather than hand-written per variant — inputs arrive as
    ``[ids, (ids_hi), s, v, a, incap, (w), q, (q_hi), cand, (cand_hi),
    (cand_w), gates]`` and outputs as ``[ids2, (ids2_hi), s2, v2, acc3,
    (w2), hit, hit_slot, placed, slot_pos]`` (parenthesised planes only
    when the matching flag is set)."""
    n_in = 8 + (2 if weighted else 0) + (3 if wide else 0)

    def kernel(*refs):
        it = iter(refs[:n_in])
        ids = next(it)[...]
        ids_hi = next(it)[...] if wide else None
        s = next(it)[...]
        v = next(it)[...]
        a = next(it)[...]
        incap = next(it)[...]
        w = next(it)[...] if weighted else None
        q = next(it)[...]
        q_hi = next(it)[...] if wide else None
        cand = next(it)[...]
        cand_hi = next(it)[...] if wide else None
        cand_w = next(it)[...] if weighted else None
        gates = next(it)[...]
        (
            ids2,
            ids2_hi,
            s2,
            v2,
            acc3,
            w2,
            hit,
            hit_slot,
            placed,
            slot_pos,
        ) = _fused_body(
            ids,
            s,
            v != 0,
            a != 0,
            incap != 0,
            w,
            q,
            cand,
            cand_w,
            *_read_gates(gates),
            ids_hi=ids_hi,
            q_hi=q_hi,
            cand_hi=cand_hi,
            **constants,
        )
        vals = [ids2]
        if wide:
            vals.append(ids2_hi)
        vals += [s2, v2.astype(jnp.int32), acc3.astype(jnp.int32)]
        if weighted:
            vals.append(w2)
        vals += [
            hit.astype(jnp.int32),
            hit_slot,
            placed.astype(jnp.int32),
            slot_pos,
        ]
        for out_ref, val in zip(refs[n_in:], vals):
            out_ref[...] = val

    return kernel


def _make_frontier_kernel(constants, weighted, wide):
    """Kernel factory for the single-launch frontier step: the fused
    score→replace→probe body of :func:`_make_fused_kernel` with the
    frontier dedup folded in front (first-occurrence + remote masks
    from the row-sorted keys) and the probe folded into one per-position
    ``code`` output (0 local/dup, 1 remote miss, 2+slot remote hit).

    Operand layout is computed from (weighted, wide): inputs ``[ids,
    (ids_hi), s, v, a, incap, (w), sk, (sk_hi), prev, (prev_hi), rem,
    cand, (cand_hi), (cand_w), gates]``, outputs ``[ids2, (ids2_hi),
    s2, v2, acc3, (w2), code, placed, slot_pos]``. In wide mode the
    first-occurrence test is a pair inequality over both word planes
    and frontier validity is ``hi >= 0``."""
    n_in = 10 + (2 if weighted else 0) + (4 if wide else 0)

    def kernel(*refs):
        it = iter(refs[:n_in])
        ids = next(it)[...]
        ids_hi = next(it)[...] if wide else None
        s = next(it)[...]
        v = next(it)[...]
        a = next(it)[...]
        incap = next(it)[...]
        w = next(it)[...] if weighted else None
        sk = next(it)[...]
        sk_hi = next(it)[...] if wide else None
        prev = next(it)[...]
        prev_hi = next(it)[...] if wide else None
        rem = next(it)[...]
        cand = next(it)[...]
        cand_hi = next(it)[...] if wide else None
        cand_w = next(it)[...] if weighted else None
        gates = next(it)[...]
        if wide:
            first = jnp.logical_and(
                jnp.logical_or(sk != prev, sk_hi != prev_hi), sk_hi >= 0
            )
        else:
            first = jnp.logical_and(sk != prev, sk >= 0)
        remote = jnp.logical_and(first, rem != 0)
        q = jnp.where(remote, sk, jnp.int32(-1))
        q_hi = jnp.where(remote, sk_hi, jnp.int32(-1)) if wide else None
        (
            ids2,
            ids2_hi,
            s2,
            v2,
            acc3,
            w2,
            hit,
            hit_slot,
            placed,
            slot_pos,
        ) = _fused_body(
            ids,
            s,
            v != 0,
            a != 0,
            incap != 0,
            w,
            q,
            cand,
            cand_w,
            *_read_gates(gates),
            ids_hi=ids_hi,
            q_hi=q_hi,
            cand_hi=cand_hi,
            **constants,
        )
        code = jnp.where(
            remote,
            jnp.where(hit, hit_slot + 2, jnp.int32(1)),
            jnp.int32(0),
        )
        vals = [ids2]
        if wide:
            vals.append(ids2_hi)
        vals += [s2, v2.astype(jnp.int32), acc3.astype(jnp.int32)]
        if weighted:
            vals.append(w2)
        vals += [code, placed.astype(jnp.int32), slot_pos]
        for out_ref, val in zip(refs[n_in:], vals):
            out_ref[...] = val

    return kernel


def _pallas_rows(kernel, operands, outs, *, interpret):
    """One ``grid=(P,)`` launch over per-PE rows.

    Every lane-padded ``(P, W)`` operand rides as ``(P, 1, W)`` with a
    ``(None, 1, W)`` block, so each program sees ``(1, W)`` rows whose
    last two block dims equal the array's. ``outs`` lists the ``(width,
    dtype)`` of each ``(P, width)`` output; returns them as ``(P, width)``
    arrays."""
    P = operands[0].shape[0]

    def spec(width):
        return pl.BlockSpec((None, 1, width), lambda i: (i, 0, 0))

    res = pl.pallas_call(
        kernel,
        grid=(P,),
        in_specs=[spec(x.shape[1]) for x in operands],
        out_specs=[spec(width) for width, _ in outs],
        out_shape=[
            jax.ShapeDtypeStruct((P, 1, width), dt) for width, dt in outs
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )(*[x[:, None, :] for x in operands])
    return [r[:, 0, :] for r in res]


def _state_operands(ids, ids_hi, scores, valid, accessed, in_capacity, weights):
    """Lane-padded buffer state with engine padding semantics
    (``valid=False``, ``in_capacity=False``, ``id=-1``): padded slots are
    never free, never stale, and never match a query."""
    ops = [_pad_lanes(ids.astype(jnp.int32), -1)]
    if ids_hi is not None:
        ops.append(_pad_lanes(ids_hi.astype(jnp.int32), -1))
    ops += [
        _pad_lanes(scores.astype(jnp.float32), 1.0),
        _pad_lanes(valid.astype(jnp.int32), 0),
        _pad_lanes(accessed.astype(jnp.int32), 0),
        _pad_lanes(in_capacity.astype(jnp.int32), 0),
    ]
    if weights is not None:
        ops.append(_pad_lanes(weights.astype(jnp.float32), 1.0))
    return ops


def _state_outs(C, wide, weighted):
    Cp = _padded(C)
    outs = [(Cp, jnp.int32)] * (2 if wide else 1)
    outs += [(Cp, jnp.float32), (Cp, jnp.int32), (Cp, jnp.int32)]
    if weighted:
        outs.append((Cp, jnp.float32))
    return outs


def _take_state(it, C, wide, weighted):
    """Unpad the state outputs: ``(ids2, ids2_hi, s2, valid2, acc3,
    w2)`` with None for the planes the configuration lacks."""
    ids2 = next(it)[:, :C]
    ids2_hi = next(it)[:, :C] if wide else None
    s2 = next(it)[:, :C]
    valid2 = next(it)[:, :C] != 0
    acc3 = next(it)[:, :C] != 0
    w2 = next(it)[:, :C] if weighted else None
    return ids2, ids2_hi, s2, valid2, acc3, w2


def _fused_step_core(
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
    gates,
    *,
    constants,
    interpret,
):
    """Shared narrow/wide launch of the staged fused step: returns the
    wide oracle's 12-tuple (``ids2_hi`` None on the narrow path)."""
    P, C = ids.shape
    M = queries.shape[1]
    K = cand.shape[1]
    wide = ids_hi is not None
    weighted = weights is not None
    operands = _state_operands(
        ids, ids_hi, scores, valid, accessed, in_capacity, weights
    )
    operands.append(_pad_lanes(queries.astype(jnp.int32), -1))
    if wide:
        operands.append(_pad_lanes(queries_hi.astype(jnp.int32), -1))
    operands.append(_pad_lanes(cand.astype(jnp.int32), -1))
    if wide:
        operands.append(_pad_lanes(cand_hi.astype(jnp.int32), -1))
    if weighted:
        operands.append(_pad_lanes(cand_weights.astype(jnp.float32), 0.0))
    operands.append(gates)
    outs = _state_outs(C, wide, weighted) + [
        (_padded(M), jnp.int32),
        (_padded(M), jnp.int32),
        (_padded(K), jnp.int32),
        (_padded(C), jnp.int32),
    ]
    it = iter(
        _pallas_rows(
            _make_fused_kernel(constants, weighted, wide),
            operands,
            outs,
            interpret=interpret,
        )
    )
    ids2, ids2_hi, s2, valid2, acc3, w2 = _take_state(it, C, wide, weighted)
    hit, hit_slot, placed, slot_pos = it
    placed_b = placed[:, :K] != 0
    return (
        ids2,
        ids2_hi,
        s2,
        valid2,
        acc3,
        w2,
        hit[:, :M] != 0,
        hit_slot[:, :M],
        placed_b,
        # The kernel's `big` sentinel uses lane-padded C/K; clamp to the
        # unpadded sentinel so outputs are bit-identical to the oracle.
        jnp.minimum(slot_pos[:, :C], jnp.int32(C + K + 1)),
        jnp.sum(placed_b.astype(jnp.int32), axis=1),
        jnp.sum(valid2.astype(jnp.int32), axis=1),
    )


_STATICS = (
    "increment",
    "decay",
    "threshold",
    "score_cap",
    "mode",
    "initial_score",
    "interpret",
)


@functools.partial(jax.jit, static_argnames=_STATICS)
def fused_step_pallas(
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
    increment: float = float(scoring.ACCESS_INCREMENT),
    decay: float = float(scoring.DECAY_FACTOR),
    threshold: float = float(scoring.STALE_THRESHOLD),
    score_cap: float = 4.0,
    mode: str = "accumulate",
    initial_score: float = float(scoring.INITIAL_SCORE),
    interpret: bool,
):
    """Pallas twin of :func:`repro.kernels.ref.fused_step` (same signature
    and outputs; see that oracle for the full semantics).

    State rows are lane-padded to multiples of 128 — at least one lane
    tile, so a zero-capacity cluster is an all-padding row — with engine
    padding semantics; ``queries``/``cand`` pad with -1 (matches
    nothing). Dispatch via :func:`repro.kernels.ops.fused_step_batch`;
    catalog entry ``docs/KERNELS.md#fused_step``.
    """
    out = _fused_step_core(
        ids,
        None,
        scores,
        valid,
        accessed,
        in_capacity,
        weights,
        queries,
        None,
        cand,
        None,
        cand_weights,
        _gate_rows(active_score, do_replace, active_probe),
        constants=_constants(
            increment, decay, threshold, score_cap, mode, initial_score
        ),
        interpret=interpret,
    )
    return out[:1] + out[2:]


@functools.partial(jax.jit, static_argnames=_STATICS)
def fused_step_wide_pallas(
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
    increment: float = float(scoring.ACCESS_INCREMENT),
    decay: float = float(scoring.DECAY_FACTOR),
    threshold: float = float(scoring.STALE_THRESHOLD),
    score_cap: float = 4.0,
    mode: str = "accumulate",
    initial_score: float = float(scoring.INITIAL_SCORE),
    interpret: bool,
):
    """Pallas twin of :func:`repro.kernels.ref.fused_step_wide` — the
    two-word ``(hi, lo)`` id encoding in the same single launch.

    Both planes lane-pad with -1 (the empty-pair sentinel), so padded
    slots/queries/candidates stay invalid under the pair semantics
    (validity is ``hi >= 0``). Returns the 12-tuple of the oracle with
    ``ids2_hi`` after ``ids2``. Dispatch via
    :func:`repro.kernels.ops.fused_step_wide_batch`.
    """
    return _fused_step_core(
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
        _gate_rows(active_score, do_replace, active_probe),
        constants=_constants(
            increment, decay, threshold, score_cap, mode, initial_score
        ),
        interpret=interpret,
    )


def _frontier_core(
    ids,
    ids_hi,
    scores,
    valid,
    accessed,
    in_capacity,
    weights,
    sk,
    sk_hi,
    prev,
    prev_hi,
    rem,
    cand,
    cand_hi,
    cw,
    gates,
    *,
    constants,
    interpret,
):
    """Shared narrow/wide per-PE core of the frontier step (padding:
    ``sk``/``prev``/``cand`` → -1, masks → 0 — a padded position is
    never first, never remote, never fresh). Returns ``(ids2, ids2_hi,
    s2, valid2, acc3, w2, code, placed, slot_pos, n_place, n_valid)``."""
    C = ids.shape[1]
    Mt = sk.shape[1]
    K = cand.shape[1]
    wide = ids_hi is not None
    weighted = weights is not None
    operands = _state_operands(
        ids, ids_hi, scores, valid, accessed, in_capacity, weights
    )
    operands.append(_pad_lanes(sk, -1))
    if wide:
        operands.append(_pad_lanes(sk_hi, -1))
    operands.append(_pad_lanes(prev, -1))
    if wide:
        operands.append(_pad_lanes(prev_hi, -1))
    operands += [
        _pad_lanes(rem.astype(jnp.int32), 0),
        _pad_lanes(cand.astype(jnp.int32), -1),
    ]
    if wide:
        operands.append(_pad_lanes(cand_hi.astype(jnp.int32), -1))
    if weighted:
        operands.append(_pad_lanes(cw.astype(jnp.float32), 0.0))
    operands.append(gates)
    outs = _state_outs(C, wide, weighted) + [
        (_padded(Mt), jnp.int32),
        (_padded(K), jnp.int32),
        (_padded(C), jnp.int32),
    ]
    it = iter(
        _pallas_rows(
            _make_frontier_kernel(constants, weighted, wide),
            operands,
            outs,
            interpret=interpret,
        )
    )
    ids2, ids2_hi, s2, valid2, acc3, w2 = _take_state(it, C, wide, weighted)
    code, placed, slot_pos = it
    placed_b = placed[:, :K] != 0
    return (
        ids2,
        ids2_hi,
        s2,
        valid2,
        acc3,
        w2,
        code[:, :Mt],
        placed_b,
        # Same sentinel clamp as the staged step: the kernel's `big`
        # uses lane-padded C/K widths.
        jnp.minimum(slot_pos[:, :C], jnp.int32(C + K + 1)),
        jnp.sum(placed_b.astype(jnp.int32), axis=1),
        jnp.sum(valid2.astype(jnp.int32), axis=1),
    )


@functools.partial(jax.jit, static_argnames=_STATICS + ("cand_cap",))
def fused_frontier_step_pallas(
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
    increment: float = float(scoring.ACCESS_INCREMENT),
    decay: float = float(scoring.DECAY_FACTOR),
    threshold: float = float(scoring.STALE_THRESHOLD),
    score_cap: float = 4.0,
    mode: str = "accumulate",
    initial_score: float = float(scoring.INITIAL_SCORE),
    interpret: bool,
):
    """Pallas twin of :func:`repro.kernels.ref.fused_frontier_step` —
    one jit dispatch per training step covers the whole pipeline.

    The (P, Mt) frontier sort, the ``part_of`` remoteness gather and the
    epilogue (miss compaction, packed readback assembly, feature-table
    payload scatter — all global gathers/sorts XLA already fuses well)
    run as jnp stages *inside this jit*; the per-PE dedup + score +
    replace + probe core runs as one ``grid=(P,)`` Pallas launch over
    lane-padded rows. An empty frontier (the run's final launch) and a
    zero-capacity buffer are padded to one lane tile like any other
    width. Outputs are bit-identical to the oracle; dispatch via
    :func:`repro.kernels.ops.fused_frontier_step_batch`. Catalog entry
    ``docs/KERNELS.md#fused_step``.
    """
    (
        active_score,
        do_replace,
        active_probe,
        sk,
        prev,
        rem,
        _remote,
    ) = _ref.frontier_prologue(touched_aug, part_of)
    cw = _ref.cand_weights_of(cand, node_weights) if weights is not None else None
    (
        ids2,
        _,
        s2,
        valid2,
        acc3,
        w2,
        code,
        placed,
        slot_pos,
        n_place,
        n_valid,
    ) = _frontier_core(
        ids,
        None,
        scores,
        valid,
        accessed,
        in_capacity,
        weights,
        sk,
        None,
        prev,
        None,
        rem,
        cand,
        None,
        cw,
        _gate_rows(active_score, do_replace, active_probe),
        constants=_constants(
            increment, decay, threshold, score_cap, mode, initial_score
        ),
        interpret=interpret,
    )
    cand_next, packed, counters, payload2 = _ref.frontier_pack(
        sk,
        code,
        placed,
        slot_pos,
        n_place,
        n_valid,
        ids2,
        payload,
        table,
        loc,
        cand_cap=cand_cap,
    )
    return (
        ids2,
        s2,
        valid2,
        acc3,
        w2,
        payload2,
        cand_next,
        packed,
        counters,
    )


@functools.partial(
    jax.jit, static_argnames=_STATICS + ("cand_cap", "id_base")
)
def fused_frontier_step_wide_pallas(
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
    id_base: int,
    increment: float = float(scoring.ACCESS_INCREMENT),
    decay: float = float(scoring.DECAY_FACTOR),
    threshold: float = float(scoring.STALE_THRESHOLD),
    score_cap: float = 4.0,
    mode: str = "accumulate",
    initial_score: float = float(scoring.INITIAL_SCORE),
    interpret: bool,
):
    """Pallas twin of :func:`repro.kernels.ref.fused_frontier_step_wide`
    — the single-launch device step over ``(hi, lo)`` word-pair ids.

    ``touched_aug`` is the raw ``(P, 2*Mt + 1)`` ``[lo | hi | gates]``
    ingest block (still one host→device transfer); the prologue's
    two-key sort, the wide ``part_of`` gather, and the wide epilogue
    (:func:`repro.kernels.ref.frontier_pack_wide`) run as jnp stages
    inside this jit while the per-PE core runs as one ``grid=(P,)``
    Pallas launch with both word planes lane-padded to -1. Outputs are
    bit-identical to the wide oracle; dispatch via
    :func:`repro.kernels.ops.fused_frontier_step_wide_batch`.
    """
    (
        active_score,
        do_replace,
        active_probe,
        sk_lo,
        sk_hi,
        prev_lo,
        prev_hi,
        rem,
        _remote,
    ) = _ref.frontier_prologue_wide(touched_aug, part_of, id_base=id_base)
    cw = (
        _ref.cand_weights_of_wide(cand, cand_hi, node_weights, id_base=id_base)
        if weights is not None
        else None
    )
    (
        ids2,
        ids2_hi,
        s2,
        valid2,
        acc3,
        w2,
        code,
        placed,
        slot_pos,
        n_place,
        n_valid,
    ) = _frontier_core(
        ids,
        ids_hi,
        scores,
        valid,
        accessed,
        in_capacity,
        weights,
        sk_lo,
        sk_hi,
        prev_lo,
        prev_hi,
        rem,
        cand,
        cand_hi,
        cw,
        _gate_rows(active_score, do_replace, active_probe),
        constants=_constants(
            increment, decay, threshold, score_cap, mode, initial_score
        ),
        interpret=interpret,
    )
    (
        cand_next_lo,
        cand_next_hi,
        packed,
        counters,
        payload2,
    ) = _ref.frontier_pack_wide(
        sk_lo,
        sk_hi,
        code,
        placed,
        slot_pos,
        n_place,
        n_valid,
        ids2,
        ids2_hi,
        payload,
        table,
        loc,
        cand_cap=cand_cap,
        id_base=id_base,
    )
    return (
        ids2,
        ids2_hi,
        s2,
        valid2,
        acc3,
        w2,
        payload2,
        cand_next_lo,
        cand_next_hi,
        packed,
        counters,
    )
