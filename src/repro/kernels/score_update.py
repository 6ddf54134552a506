"""Pallas TPU kernels: fused scoring-policy rounds.

One VMEM pass over the whole buffer applies a scoring policy
(access -> gain, idle -> decay) and simultaneously reduces the stale
count (score < threshold) the prefetcher uses to decide whether a
replacement round would even find victims. On GPU this is two
elementwise launches plus a reduction; fusing matters at 10^6-slot
buffers where the score array no longer fits L2/VMEM at once.

``score_update`` / ``score_update_batch`` are the paper's fixed policy
(+1 on access, x0.95 idle, stale < 0.95). ``score_policy_update_batch``
generalizes the same fused pass over the policy zoo in
:mod:`repro.core.scoring`: the update mode (accumulate / reset / capped)
and its constants are compile-time parameters, and the degree policy's
per-slot access weights ride along as an optional third VMEM operand.

Grid: (tiles,) over an (8, 128)-aligned 2-D view of the buffer.

Catalog entry: ``docs/KERNELS.md#score_update``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import scoring

LANES = 128
SUBLANES = 8
TILE_ROWS = 64  # (64, 128) f32 tile = 32 KiB VMEM


def _stale_lanes(new, threshold):
    """Per-lane stale counts of one tile as a ``(1, 128)`` row."""
    return jnp.sum(
        (new < jnp.float32(threshold)).astype(jnp.int32), axis=0, keepdims=True
    )


#: Per-tile partial stale counts: one ``(1, 128)`` row of lane sums per
#: tile (Mosaic needs a count block whose last two dims are the array's).
_STALE_SPEC = pl.BlockSpec((None, 1, LANES), lambda i: (i, 0, 0))


def _score_kernel(scores_ref, accessed_ref, out_ref, stale_ref):
    s = scores_ref[...]
    a = accessed_ref[...] != 0
    new = jnp.where(
        a,
        s + jnp.float32(scoring.ACCESS_INCREMENT),
        s * jnp.float32(scoring.DECAY_FACTOR),
    )
    out_ref[...] = new
    stale_ref[...] = _stale_lanes(new, scoring.STALE_THRESHOLD)


@functools.partial(jax.jit, static_argnames=("interpret",))
def score_update(
    scores: jax.Array, accessed: jax.Array, *, interpret: bool
) -> tuple[jax.Array, jax.Array]:
    """scores (N,) f32, accessed (N,) bool -> (new_scores (N,), stale_count).

    Padding rows use score=1.0 / accessed=False so they never count as
    stale within the padded region... they decay to 0.95 (not < 0.95).
    """
    n = scores.shape[0]
    row = TILE_ROWS * LANES
    pad = (row - n % row) % row
    s2 = jnp.pad(scores.astype(jnp.float32), (0, pad), constant_values=1.0)
    a2 = jnp.pad(accessed.astype(jnp.int32), (0, pad), constant_values=1)
    tiles = s2.shape[0] // row
    s2 = s2.reshape(tiles * TILE_ROWS, LANES)
    a2 = a2.reshape(tiles * TILE_ROWS, LANES)

    new, stale_partial = pl.pallas_call(
        _score_kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0)),
            _STALE_SPEC,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tiles * TILE_ROWS, LANES), jnp.float32),
            jax.ShapeDtypeStruct((tiles, 1, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(s2, a2)
    new_scores = new.reshape(-1)[:n]
    # Padded lanes were (1.0, accessed) -> 2.0, never stale.
    return new_scores, jnp.sum(stale_partial)


@functools.partial(jax.jit, static_argnames=("interpret",))
def score_update_batch(
    scores: jax.Array, accessed: jax.Array, *, interpret: bool
) -> tuple[jax.Array, jax.Array]:
    """Multi-PE scoring round: scores (P, N) f32, accessed (P, N) bool
    -> (new_scores (P, N), stale_count (P,)).

    The multi-trainer runtime (:class:`repro.runtime.PrefetchEngine`)
    holds every PE's buffer in one dense array; this wrapper pads each
    PE's row to a whole number of (TILE_ROWS, LANES) tiles so the fused
    single-buffer kernel runs unchanged over the concatenated grid, then
    reduces the per-tile stale counts back to one count per PE.
    """
    P, n = scores.shape
    row = TILE_ROWS * LANES
    pad = (row - n % row) % row
    s2 = jnp.pad(
        scores.astype(jnp.float32), ((0, 0), (0, pad)), constant_values=1.0
    )
    a2 = jnp.pad(accessed.astype(jnp.int32), ((0, 0), (0, pad)), constant_values=1)
    tiles_per_pe = s2.shape[1] // row
    tiles = P * tiles_per_pe
    s2 = s2.reshape(tiles * TILE_ROWS, LANES)
    a2 = a2.reshape(tiles * TILE_ROWS, LANES)

    new, stale_partial = pl.pallas_call(
        _score_kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0)),
            _STALE_SPEC,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tiles * TILE_ROWS, LANES), jnp.float32),
            jax.ShapeDtypeStruct((tiles, 1, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(s2, a2)
    new_scores = new.reshape(P, -1)[:, :n]
    return new_scores, jnp.sum(stale_partial.reshape(P, -1), axis=1)


# --------------------------------------------------------------------- #
# Policy-zoo generalization
# --------------------------------------------------------------------- #
def _policy_kernel_body(s, a, w, *, increment, decay, score_cap, mode):
    """Shared update rule; mirrors ``ScoringPolicy.update`` bit-for-bit."""
    gain = jnp.float32(increment)
    if w is not None:
        gain = gain * w
    if mode == "accumulate":
        touched = s + gain
    elif mode == "reset":
        # + 0 broadcasts the (possibly scalar) gain to the tile shape
        # without perturbing the float32 value.
        touched = gain + jnp.zeros_like(s)
    else:  # capped
        touched = jnp.minimum(s + gain, jnp.float32(score_cap))
    return jnp.where(a, touched, s * jnp.float32(decay))


def _make_policy_kernel(increment, decay, threshold, score_cap, mode, weighted):
    if weighted:

        def kernel(scores_ref, accessed_ref, weights_ref, out_ref, stale_ref):
            new = _policy_kernel_body(
                scores_ref[...],
                accessed_ref[...] != 0,
                weights_ref[...],
                increment=increment,
                decay=decay,
                score_cap=score_cap,
                mode=mode,
            )
            out_ref[...] = new
            stale_ref[...] = _stale_lanes(new, threshold)

    else:

        def kernel(scores_ref, accessed_ref, out_ref, stale_ref):
            new = _policy_kernel_body(
                scores_ref[...],
                accessed_ref[...] != 0,
                None,
                increment=increment,
                decay=decay,
                score_cap=score_cap,
                mode=mode,
            )
            out_ref[...] = new
            stale_ref[...] = _stale_lanes(new, threshold)

    return kernel


def _pad_tiles_2d(x, pad, constant):
    return jnp.pad(x, ((0, 0), (0, pad)), constant_values=constant)


@functools.partial(
    jax.jit,
    static_argnames=(
        "increment",
        "decay",
        "threshold",
        "score_cap",
        "mode",
        "interpret",
    ),
)
def _score_policy_jit(
    scores,
    accessed,
    weights,
    *,
    increment,
    decay,
    threshold,
    score_cap,
    mode,
    interpret,
):
    P, n = scores.shape
    row = TILE_ROWS * LANES
    pad = (row - n % row) % row
    # Padded lanes are (score=1, accessed, weight=1): their post-update
    # value is >= threshold for every zoo policy (checked by the public
    # wrapper), so they never contribute to the stale counts.
    s2 = _pad_tiles_2d(scores.astype(jnp.float32), pad, 1.0)
    a2 = _pad_tiles_2d(accessed.astype(jnp.int32), pad, 1)
    tiles_per_pe = s2.shape[1] // row
    tiles = P * tiles_per_pe
    s2 = s2.reshape(tiles * TILE_ROWS, LANES)
    a2 = a2.reshape(tiles * TILE_ROWS, LANES)
    operands = [s2, a2]
    weighted = weights is not None
    if weighted:
        w2 = _pad_tiles_2d(weights.astype(jnp.float32), pad, 1.0)
        operands.append(w2.reshape(tiles * TILE_ROWS, LANES))

    block = pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0))
    new, stale_partial = pl.pallas_call(
        _make_policy_kernel(
            increment, decay, threshold, score_cap, mode, weighted
        ),
        grid=(tiles,),
        in_specs=[block] * len(operands),
        out_specs=[block, _STALE_SPEC],
        out_shape=[
            jax.ShapeDtypeStruct((tiles * TILE_ROWS, LANES), jnp.float32),
            jax.ShapeDtypeStruct((tiles, 1, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(*operands)
    new_scores = new.reshape(P, -1)[:, :n]
    return new_scores, jnp.sum(stale_partial.reshape(P, -1), axis=1)


def score_policy_update_batch(
    scores: jax.Array,
    accessed: jax.Array,
    weights: jax.Array | None = None,
    *,
    increment: float = float(scoring.ACCESS_INCREMENT),
    decay: float = float(scoring.DECAY_FACTOR),
    threshold: float = float(scoring.STALE_THRESHOLD),
    mode: str = "accumulate",
    score_cap: float = 4.0,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Policy-zoo scoring round: scores (P, N) f32, accessed (P, N) bool
    [, weights (P, N) f32] -> (new_scores (P, N), stale_count (P,)).

    ``mode``/constants follow :class:`repro.core.scoring.ScoringPolicy`;
    the default parameters reproduce ``score_update_batch`` exactly.
    """
    if mode not in scoring.MODES:
        raise ValueError(f"mode must be one of {scoring.MODES}, got {mode!r}")
    # Post-update value of a padded lane (score=1, accessed, weight=1).
    if mode == "accumulate":
        pad_value = 1.0 + increment
    elif mode == "reset":
        pad_value = increment
    else:
        pad_value = min(1.0 + increment, score_cap)
    if pad_value < threshold:
        raise ValueError(
            f"policy (mode={mode!r}, increment={increment}, "
            f"score_cap={score_cap}) would mark padding lanes stale "
            f"(post-update {pad_value} < threshold {threshold})"
        )
    return _score_policy_jit(
        scores,
        accessed,
        weights,
        increment=float(increment),
        decay=float(decay),
        threshold=float(threshold),
        score_cap=float(score_cap),
        mode=mode,
        interpret=interpret,
    )
