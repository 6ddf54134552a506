"""Pallas TPU kernel: tiled row gather (persistent-buffer feature fetch).

The paper's minibatch assembly gathers feature rows of buffered remote
nodes (Algorithm 1 line 11, ``BUF ∩ S``). On GPU this is a global-memory
gather; the TPU-native formulation streams the row indices through SMEM
(``PrefetchScalarGridSpec``) and lets the BlockSpec index_map select one
HBM row block per grid step, so each (1, F_tile) tile lands in VMEM
aligned to the (8, 128) lane layout with no scatter/atomic machinery.

Grid: (M rows, F/F_TILE feature tiles).

Catalog entry: ``docs/KERNELS.md#gather_rows``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F_TILE = 512  # lane-aligned feature tile (multiple of 128)


def _gather_kernel(idx_ref, table_ref, out_ref):
    # table_ref block: (1, F_TILE) — the row selected by index_map.
    out_ref[...] = table_ref[...]


def _row_index_map(i, j, idx_ref):
    return idx_ref[i], j


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(
    table: jax.Array, indices: jax.Array, *, interpret: bool
) -> jax.Array:
    """table (N, F), indices (M,) int32 -> (M, F).

    ``interpret=True`` executes the kernel body in Python on CPU (this
    container); on real TPU pass ``interpret=False``.
    """
    n, f = table.shape
    m = indices.shape[0]
    f_pad = (F_TILE - f % F_TILE) % F_TILE
    table_p = jnp.pad(table, ((0, 0), (0, f_pad))) if f_pad else table
    fp = f + f_pad

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m, fp // F_TILE),
        in_specs=[
            pl.BlockSpec((1, F_TILE), _row_index_map),
        ],
        out_specs=pl.BlockSpec((1, F_TILE), lambda i, j, idx_ref: (i, j)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, fp), table.dtype),
        interpret=interpret,
    )(indices.astype(jnp.int32), table_p)
    return out[:, :f]


def _batch_row_index_map(p, i, j, idx_ref):
    return p, idx_ref[p, i], 0, j


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows_batch(
    tables: jax.Array, indices: jax.Array, *, interpret: bool
) -> jax.Array:
    """tables (P, N, F), indices (P, M) int32 -> (P, M, F).

    Multi-PE variant for the vectorized runtime: every trainer PE's
    buffer payload is one leading-axis slice of ``tables`` and its fetch
    list one row of ``indices``; the grid gains a leading PE dimension
    and the scalar-prefetched index map picks (PE, row) per step. Rows
    ride as ``(P, N, 1, F)`` so each ``(1, F_TILE)`` block spans the
    array's own last-but-one dim, as Mosaic's block rule requires.
    """
    P, n, f = tables.shape
    m = indices.shape[1]
    f_pad = (F_TILE - f % F_TILE) % F_TILE
    tables_p = (
        jnp.pad(tables, ((0, 0), (0, 0), (0, f_pad))) if f_pad else tables
    )
    fp = f + f_pad

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(P, m, fp // F_TILE),
        in_specs=[
            pl.BlockSpec((None, None, 1, F_TILE), _batch_row_index_map),
        ],
        out_specs=pl.BlockSpec(
            (None, None, 1, F_TILE), lambda p, i, j, idx_ref: (p, i, 0, j)
        ),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, m, 1, fp), tables.dtype),
        interpret=interpret,
    )(indices.astype(jnp.int32), tables_p[:, :, None, :])
    return out[:, :, 0, :f]
