"""Pallas TPU kernel: flash-decode for MLA latent attention.

DeepSeek's absorbed-matrices decode attends in the compressed latent
space: queries (B, H, r_kv) against the latent cache (B, S, r_kv) plus a
shared rope channel (B, S, r_rope). The XLA lowering materialises the
full (B, H, S) score tensor in f32 (134 MB/chip/layer at 32k) and reads
the cache twice (scores, then context). This kernel is the classic
flash-decode reformulation: the sequence axis is tiled, each tile's
scores feed an ONLINE softmax (running max m, normaliser l, accumulator
acc in VMEM scratch), and the latent cache streams HBM->VMEM exactly
once. §Perf C logged this as the next step after full-mesh EP.

Grid: (B, S/S_TILE) — TPU iterates the trailing grid dim sequentially,
so scratch carries the running softmax across sequence tiles.

Catalog entry: ``docs/KERNELS.md#mla_decode``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

S_TILE = 256
NEG_INF = -2.3819763e38


def _mla_decode_kernel(
    pos_ref,            # scalar prefetch: (1,) int32 current length
    q_lat_ref,          # (1, H, r)
    q_rope_ref,         # (1, H, rr)
    c_ref,              # (1, S_TILE, r)
    kr_ref,             # (1, S_TILE, rr)
    out_ref,            # (1, H, r)
    m_ref,              # scratch (H, 1) f32 running max
    l_ref,              # scratch (H, 1) f32 running normaliser
    acc_ref,            # scratch (H, r) f32 running context
    *,
    scale: float,
):
    j = pl.program_id(1)
    n_tiles = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lat = q_lat_ref[0].astype(jnp.float32)      # (H, r)
    q_rope = q_rope_ref[0].astype(jnp.float32)    # (H, rr)
    c = c_ref[0].astype(jnp.float32)              # (S_TILE, r)
    kr = kr_ref[0].astype(jnp.float32)            # (S_TILE, rr)

    scores = (
        jnp.dot(q_lat, c.T, preferred_element_type=jnp.float32)
        + jnp.dot(q_rope, kr.T, preferred_element_type=jnp.float32)
    ) * scale                                      # (H, S_TILE)

    s_idx = j * S_TILE + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(s_idx <= pos_ref[0], scores, NEG_INF)

    m_prev = m_ref[...]                            # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    # Guard fully-masked tiles: exp(NEG_INF - NEG_INF) would be NaN.
    safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    alpha = jnp.where(
        m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - safe_m)
    )                                              # (H, 1)
    p = jnp.exp(scores - safe_m)                   # (H, S_TILE)
    p = jnp.where(s_idx <= pos_ref[0], p, 0.0)

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, c, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(j == n_tiles - 1)
    def _finish():
        out_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "scale"))
def mla_flash_decode(
    q_lat: jax.Array,            # (B, H, r)
    q_rope: jax.Array,           # (B, H, rr)
    cache_c: jax.Array,          # (B, S, r)
    cache_kr: jax.Array,         # (B, S, rr)
    pos: jax.Array,              # scalar int32 — current length (inclusive)
    *,
    scale: float | None = None,
    interpret: bool,
) -> jax.Array:
    """Returns the latent context (B, H, r) = softmax(scores) @ cache_c."""
    b, h, r = q_lat.shape
    rr = q_rope.shape[-1]
    s = cache_c.shape[1]
    pad = (S_TILE - s % S_TILE) % S_TILE
    if pad:
        cache_c = jnp.pad(cache_c, ((0, 0), (0, pad), (0, 0)))
        cache_kr = jnp.pad(cache_kr, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    if scale is None:
        scale = 1.0 / (r + rr) ** 0.5  # caller usually passes the qk scale

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, sp // S_TILE),
        in_specs=[
            pl.BlockSpec((1, h, r), lambda i, j, pos_ref: (i, 0, 0)),
            pl.BlockSpec((1, h, rr), lambda i, j, pos_ref: (i, 0, 0)),
            pl.BlockSpec((1, S_TILE, r), lambda i, j, pos_ref: (i, j, 0)),
            pl.BlockSpec((1, S_TILE, rr), lambda i, j, pos_ref: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, r), lambda i, j, pos_ref: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, r), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), cache_c.dtype),
        interpret=interpret,
    )(
        jnp.asarray(pos, jnp.int32).reshape(1),
        q_lat,
        q_rope,
        cache_c,
        cache_kr,
    )
