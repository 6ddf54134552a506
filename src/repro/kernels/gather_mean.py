"""Pallas TPU kernel: fused gather + mean — GraphSAGE neighbor aggregation.

The GNN hot loop gathers each destination's K sampled neighbor feature
rows and mean-reduces them (``mean(x_neighbors)`` in
``gnn.sage``). The CUDA idiom is gather + atomicAdd scatter; TPU has no
atomics, so the kernel is re-blocked destination-major: one grid step
owns one destination row, its K neighbor indices arrive via SMEM scalar
prefetch, and the K rows are accumulated in a VMEM accumulator tile —
a single pass, no intermediate (B, K, F) materialisation.

Grid: (B destinations, F/F_TILE feature tiles); K unrolled (static fanout).

Catalog entry: ``docs/KERNELS.md#gather_mean``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F_TILE = 512


def _make_kernel(k: int):
    def kernel(idx_ref, *refs):
        # refs: k table views (1, F_TILE) selected per neighbor, out (1, F_TILE)
        out_ref = refs[-1]
        acc = refs[0][...].astype(jnp.float32)
        for j in range(1, k):
            acc = acc + refs[j][...].astype(jnp.float32)
        out_ref[...] = (acc * (1.0 / k)).astype(out_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_mean(
    table: jax.Array, indices: jax.Array, *, interpret: bool
) -> jax.Array:
    """table (N, F), indices (B, K) -> (B, F) mean of gathered rows."""
    n, f = table.shape
    b, k = indices.shape
    f_pad = (F_TILE - f % F_TILE) % F_TILE
    table_p = jnp.pad(table, ((0, 0), (0, f_pad))) if f_pad else table
    fp = f + f_pad

    def nbr_index_map(slot):
        def index_map(i, j, idx_ref):
            return idx_ref[i, slot], j

        return index_map

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, fp // F_TILE),
        in_specs=[
            pl.BlockSpec((1, F_TILE), nbr_index_map(slot)) for slot in range(k)
        ],
        out_specs=pl.BlockSpec((1, F_TILE), lambda i, j, idx_ref: (i, j)),
    )
    out = pl.pallas_call(
        _make_kernel(k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, fp), table.dtype),
        interpret=interpret,
    )(indices.astype(jnp.int32), *([table_p] * k))
    return out[:, :f]
