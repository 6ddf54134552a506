"""Pallas TPU kernel: fused multi-PE frontier dedup + remote extraction.

The sampling plane (:class:`repro.graph.sampler.SamplerPlane`) row-sorts
all P trainers' sampled frontiers into one ``(P, M)`` block; what
remains per minibatch is the dedup/membership pass the legacy path did
P times with ``np.unique`` + a partition filter: mark each row's
first occurrences (the sorted-unique elements) and, fused in the same
pass, the unique elements homed on another partition (the remote fetch
set), plus the per-PE counts used to split the ragged extraction.

One VMEM pass computes all four outputs — on GPU/TPU this is otherwise
two elementwise launches and two reductions over a block that, at
production scale (P trainers x batch x f1 x f2 frontier slots), no
longer fits L2/VMEM at once.

Inputs are the *sorted* keys; the neighbor-shift operand is built by the
wrapper (a roll at the jnp level), so the kernel body is purely
elementwise + reduce and tiles exactly like the scoring kernels.

Grid: (tiles,) over an (8, 128)-aligned 2-D view, one ``(1, 128)`` row
of per-lane partial counts per tile, reduced back to one count per PE.

Catalog entry: ``docs/KERNELS.md#frontier_unique``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
TILE_ROWS = 64  # (64, 128) i32 tile = 32 KiB VMEM per operand

#: Padding key: equal in ``keys`` and ``prev`` so padded lanes are never
#: "first". Real keys (node ids) are >= 0.
_PAD_KEY = -2


def _frontier_kernel(keys_ref, prev_ref, remote_ref, first_ref, rmask_ref,
                     ucount_ref, rcount_ref):
    k = keys_ref[...]
    first = (k != prev_ref[...]).astype(jnp.int32)
    rmask = first * remote_ref[...]
    first_ref[...] = first
    rmask_ref[...] = rmask
    ucount_ref[...] = jnp.sum(first, axis=0, keepdims=True)
    rcount_ref[...] = jnp.sum(rmask, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def frontier_unique_batch(
    sorted_keys: jax.Array, is_remote: jax.Array, *, interpret: bool
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused unique + remote masks over row-sorted frontiers.

    ``sorted_keys`` is ``(P, M)`` int32, each row ascending, keys >= 0;
    ``is_remote`` is ``(P, M)`` bool/int32 (``part_of[key] != p`` per
    row). Returns ``(first_mask (P, M) bool, remote_mask (P, M) bool,
    unique_count (P,) int32, remote_count (P,) int32)`` where
    ``first_mask`` selects each row's sorted-unique elements and
    ``remote_mask = first_mask & is_remote``.
    """
    P, M = sorted_keys.shape
    if M == 0:
        empty = jnp.zeros((P, 0), dtype=bool)
        zeros = jnp.zeros((P,), dtype=jnp.int32)
        return empty, empty, zeros, zeros
    k = sorted_keys.astype(jnp.int32)
    prev = jnp.concatenate(
        [jnp.full((P, 1), -1, dtype=jnp.int32), k[:, :-1]], axis=1
    )
    row = TILE_ROWS * LANES
    pad = (row - M % row) % row
    k2 = jnp.pad(k, ((0, 0), (0, pad)), constant_values=_PAD_KEY)
    p2 = jnp.pad(prev, ((0, 0), (0, pad)), constant_values=_PAD_KEY)
    r2 = jnp.pad(
        is_remote.astype(jnp.int32), ((0, 0), (0, pad)), constant_values=0
    )
    tiles_per_pe = k2.shape[1] // row
    tiles = P * tiles_per_pe
    k2 = k2.reshape(tiles * TILE_ROWS, LANES)
    p2 = p2.reshape(tiles * TILE_ROWS, LANES)
    r2 = r2.reshape(tiles * TILE_ROWS, LANES)

    block = pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0))
    count = pl.BlockSpec((None, 1, LANES), lambda i: (i, 0, 0))
    first, rmask, ucount, rcount = pl.pallas_call(
        _frontier_kernel,
        grid=(tiles,),
        in_specs=[block, block, block],
        out_specs=[block, block, count, count],
        out_shape=[
            jax.ShapeDtypeStruct((tiles * TILE_ROWS, LANES), jnp.int32),
            jax.ShapeDtypeStruct((tiles * TILE_ROWS, LANES), jnp.int32),
            jax.ShapeDtypeStruct((tiles, 1, LANES), jnp.int32),
            jax.ShapeDtypeStruct((tiles, 1, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(k2, p2, r2)
    first = first.reshape(P, -1)[:, :M].astype(bool)
    rmask = rmask.reshape(P, -1)[:, :M].astype(bool)
    ucount = jnp.sum(ucount.reshape(P, -1), axis=1)
    rcount = jnp.sum(rcount.reshape(P, -1), axis=1)
    return first, rmask, ucount, rcount


def _frontier_kernel_wide(
    keys_lo_ref,
    keys_hi_ref,
    prev_lo_ref,
    prev_hi_ref,
    remote_ref,
    first_ref,
    rmask_ref,
    ucount_ref,
    rcount_ref,
):
    kl = keys_lo_ref[...]
    kh = keys_hi_ref[...]
    first = jnp.logical_or(
        kl != prev_lo_ref[...], kh != prev_hi_ref[...]
    ).astype(jnp.int32)
    rmask = first * remote_ref[...]
    first_ref[...] = first
    rmask_ref[...] = rmask
    ucount_ref[...] = jnp.sum(first, axis=0, keepdims=True)
    rcount_ref[...] = jnp.sum(rmask, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def frontier_unique_batch_wide(
    sorted_lo: jax.Array,
    sorted_hi: jax.Array,
    is_remote: jax.Array,
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Wide-id twin of :func:`frontier_unique_batch`: rows are sorted
    ``(hi, lo)`` int32 word-pair planes (numeric 64-bit order under the
    lexicographic two-key sort — see ``kernels/ref.py`` ``WIDE_SHIFT``),
    so first-occurrence is a pair inequality against the row-shifted
    neighbours. Same outputs and tiling as the narrow kernel; both
    planes pad with :data:`_PAD_KEY` so padded lanes are never first.
    """
    P, M = sorted_lo.shape
    if M == 0:
        empty = jnp.zeros((P, 0), dtype=bool)
        zeros = jnp.zeros((P,), dtype=jnp.int32)
        return empty, empty, zeros, zeros
    kl = sorted_lo.astype(jnp.int32)
    kh = sorted_hi.astype(jnp.int32)
    neg = jnp.full((P, 1), -1, dtype=jnp.int32)
    prev_lo = jnp.concatenate([neg, kl[:, :-1]], axis=1)
    prev_hi = jnp.concatenate([neg, kh[:, :-1]], axis=1)
    row = TILE_ROWS * LANES
    pad = (row - M % row) % row

    def _pad(x, constant):
        return jnp.pad(x, ((0, 0), (0, pad)), constant_values=constant)

    kl2, kh2 = _pad(kl, _PAD_KEY), _pad(kh, _PAD_KEY)
    pl2, ph2 = _pad(prev_lo, _PAD_KEY), _pad(prev_hi, _PAD_KEY)
    r2 = _pad(is_remote.astype(jnp.int32), 0)
    tiles_per_pe = kl2.shape[1] // row
    tiles = P * tiles_per_pe
    kl2 = kl2.reshape(tiles * TILE_ROWS, LANES)
    kh2 = kh2.reshape(tiles * TILE_ROWS, LANES)
    pl2 = pl2.reshape(tiles * TILE_ROWS, LANES)
    ph2 = ph2.reshape(tiles * TILE_ROWS, LANES)
    r2 = r2.reshape(tiles * TILE_ROWS, LANES)

    block = pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0))
    count = pl.BlockSpec((None, 1, LANES), lambda i: (i, 0, 0))
    first, rmask, ucount, rcount = pl.pallas_call(
        _frontier_kernel_wide,
        grid=(tiles,),
        in_specs=[block, block, block, block, block],
        out_specs=[block, block, count, count],
        out_shape=[
            jax.ShapeDtypeStruct((tiles * TILE_ROWS, LANES), jnp.int32),
            jax.ShapeDtypeStruct((tiles * TILE_ROWS, LANES), jnp.int32),
            jax.ShapeDtypeStruct((tiles, 1, LANES), jnp.int32),
            jax.ShapeDtypeStruct((tiles, 1, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(kl2, kh2, pl2, ph2, r2)
    first = first.reshape(P, -1)[:, :M].astype(bool)
    rmask = rmask.reshape(P, -1)[:, :M].astype(bool)
    ucount = jnp.sum(ucount.reshape(P, -1), axis=1)
    rcount = jnp.sum(rcount.reshape(P, -1), axis=1)
    return first, rmask, ucount, rcount
