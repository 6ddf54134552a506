"""Pallas TPU kernel: sorted-segment sum (full-graph SAGE aggregation).

For the full-graph (CSR, variable-degree) aggregation path the CUDA
idiom is scatter-add with atomics. TPU has no atomics; the re-blocked
formulation exploits that the sampler emits edges **sorted by
destination segment**: the grid walks edge tiles in order, a VMEM
accumulator carries the running row sum, and each output segment is
written when the sweep crosses its boundary. Here we implement the
equal-degree specialisation (edges per segment = K, the padded-fanout
layout our sampler produces), where segment boundaries are static:
one grid step = one destination tile, K edge rows reduced in VMEM.

Grid: (segments/SEG_TILE, F/F_TILE).

Catalog entry: ``docs/KERNELS.md#segment_sum``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F_TILE = 256
SEG_TILE = 8


def _make_kernel(k: int):
    def kernel(data_ref, out_ref):
        # data block: (SEG_TILE * k, F_TILE); reduce every k consecutive rows.
        block = data_ref[...].astype(jnp.float32)
        block = block.reshape(SEG_TILE, k, F_TILE)
        out_ref[...] = jnp.sum(block, axis=1).astype(out_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def segment_sum_equal(
    data: jax.Array, k: int, *, interpret: bool
) -> jax.Array:
    """data (S*k, F) sorted by segment, k rows per segment -> (S, F)."""
    e, f = data.shape
    assert e % k == 0, (e, k)
    s = e // k
    f_pad = (F_TILE - f % F_TILE) % F_TILE
    s_pad = (SEG_TILE - s % SEG_TILE) % SEG_TILE
    data_p = jnp.pad(data, ((0, s_pad * k), (0, f_pad)))
    sp, fp = s + s_pad, f + f_pad

    out = pl.pallas_call(
        _make_kernel(k),
        grid=(sp // SEG_TILE, fp // F_TILE),
        in_specs=[pl.BlockSpec((SEG_TILE * k, F_TILE), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((SEG_TILE, F_TILE), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((sp, fp), data.dtype),
        interpret=interpret,
    )(data_p)
    return out[:s, :f]
