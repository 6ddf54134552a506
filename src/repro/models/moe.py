"""Mixture-of-Experts layer.

Covers DeepSeek-V3 (1 shared + 256 routed, top-8, gates normalised over
the selected experts) and Phi-3.5-MoE (16 routed, top-2). Router runs in
fp32; a Switch-style load-balance auxiliary loss is returned for
training.

Three execution paths:

* ``moe_forward`` — single-device dropless dispatch: sort token copies
  by expert, grouped GEMMs via ``jax.lax.ragged_dot`` (the TPU gmm
  path), scatter-add back. Used by CPU tests/examples.
* ``moe_forward_ep`` + ``_moe_local_body`` — expert parallelism under
  ``shard_map``: experts sharded over ``cfg.ep_axis`` (one axis for
  training, the full mesh for decode); tokens replicated over the ep
  axis; each device computes its experts' token copies in
  fixed-capacity dense blocks (``_expert_ffn_blocked`` — exact FLOPs,
  unlike ragged_dot's per-group full-length lowering, see EXPERIMENTS.md
  §Perf) and psum-combines.
* ``_moe_local_body_a2a`` (``ep_combine='a2a'``) — sequence-sharded
  activations with two all-to-alls moving only the routed copies; the
  beyond-paper collective schedule from §Perf iteration 4.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import dtype_of, init_dense
from .config import ModelConfig
from .mlp import init_mlp, mlp_forward

def init_moe(cfg: ModelConfig, key) -> dict:
    dt = dtype_of(cfg)
    m = cfg.moe
    d, e, f = cfg.d_model, m.num_experts, m.d_ff_expert
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)

    def expert_stack(k, a, b):
        return (
            jax.random.normal(k, (e, a, b), jnp.float32) * (1.0 / a) ** 0.5
        ).astype(dt)

    params = {
        "router": jax.random.normal(k1, (d, e), jnp.float32) * 0.02,
        "w_gate": expert_stack(k2, d, f),
        "w_up": expert_stack(k3, d, f),
        "w_down": expert_stack(k4, f, d),
    }
    if m.num_shared_experts:
        params["shared"] = init_mlp(cfg, k5, d_ff=f * m.num_shared_experts)
    return params


def _route(cfg: ModelConfig, router: jax.Array, tokens: jax.Array):
    """Top-k gates in fp32. DeepSeek normalises the selected gates."""
    m = cfg.moe
    logits = jnp.einsum("nd,de->ne", tokens.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.experts_per_token)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e.
    e = m.num_experts
    density = jnp.mean(
        jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0
    )
    mean_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * mean_probs)
    return gates, idx, aux


def moe_forward(
    cfg: ModelConfig, params: dict, x: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) → (y, aux_loss)."""
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    k = m.experts_per_token
    tokens = x.reshape(n, d)

    gates, idx, aux = _route(cfg, params["router"], tokens)

    # Sort token copies by expert id → grouped GEMM over contiguous rows.
    flat_expert = idx.reshape(-1)                       # (n*k,)
    order = jnp.argsort(flat_expert)                    # stable
    token_of = order // k                               # source token row
    xs = jnp.take(tokens, token_of, axis=0)             # (n*k, d)
    group_sizes = jnp.bincount(flat_expert, length=m.num_experts)

    up = jax.lax.ragged_dot(xs, params["w_up"], group_sizes)
    if cfg.mlp_type in ("swiglu", "geglu"):
        gate = jax.lax.ragged_dot(xs, params["w_gate"], group_sizes)
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else (
            lambda z: jax.nn.gelu(z, approximate=True)
        )
        h = act(gate) * up
    elif cfg.mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(up))
    else:
        h = jax.nn.gelu(up, approximate=True)
    out = jax.lax.ragged_dot(h, params["w_down"], group_sizes)  # (n*k, d)

    gate_of = jnp.take(gates.reshape(-1), order)        # (n*k,)
    y = jnp.zeros((n, d), dtype=out.dtype)
    y = y.at[token_of].add(out * gate_of[:, None].astype(out.dtype))
    y = y.reshape(b, s, d).astype(x.dtype)

    if m.num_shared_experts:
        y = y + mlp_forward(cfg, params["shared"], x)
    return y, aux.astype(jnp.float32)


# --------------------------------------------------------------------- #
# Expert-parallel path (shard_map over the 'model' axis)
# --------------------------------------------------------------------- #
def _expert_ffn(cfg: ModelConfig, w_gate, w_up, w_down, xs, group_sizes):
    up = jax.lax.ragged_dot(xs, w_up, group_sizes)
    if cfg.mlp_type in ("swiglu", "geglu"):
        gate = jax.lax.ragged_dot(xs, w_gate, group_sizes)
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else (
            lambda z: jax.nn.gelu(z, approximate=True)
        )
        h = act(gate) * up
    elif cfg.mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(up))
    else:
        h = jax.nn.gelu(up, approximate=True)
    return jax.lax.ragged_dot(h, w_down, group_sizes)


def _expert_ffn_blocked(cfg: ModelConfig, w_gate, w_up, w_down, xb):
    """Batched dense expert FFN over fixed-capacity blocks.

    xb: (E_local, cap_e, D). §Perf iteration: ``ragged_dot`` lowers to
    per-group FULL-length dots on this backend (e_local x the FLOPs);
    the blocked einsum pays exactly cap x D x F per matmul.
    """
    up = jnp.einsum("ecd,edf->ecf", xb, w_up)
    if cfg.mlp_type in ("swiglu", "geglu"):
        gate = jnp.einsum("ecd,edf->ecf", xb, w_gate)
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else (
            lambda z: jax.nn.gelu(z, approximate=True)
        )
        h = act(gate) * up
    elif cfg.mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(up))
    else:
        h = jax.nn.gelu(up, approximate=True)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _axis_index_flat(names) -> jax.Array:
    """Linear device index along one axis name or a tuple of them."""
    if isinstance(names, str):
        return jax.lax.axis_index(names)
    idx = jnp.int32(0)
    for nm in names:
        idx = idx * jax.lax.psum(1, nm) + jax.lax.axis_index(nm)
    return idx


def _moe_local_body(cfg: ModelConfig, axis_names, router, w_gate, w_up, w_down, x_blk):
    """Per-device body under shard_map.

    x_blk: (B_local, S, D) — tokens replicated across the ep axis.
    w_*:   (E_local, ...)  — this device's expert shard.

    Routing runs in-body on the replicated tokens (each ep column
    computes identical routing — ~4% of step FLOPs; §Perf iteration 2
    tried sharding it data x model outside the body, which triggered
    XLA's involuntary-full-remat resharding and 280+ GB of f32
    activation all-gathers — refuted, reverted). Each device computes
    only the token-copies assigned to ITS experts in fixed-capacity
    dense blocks; partial outputs psum-combine over the ep axis.

    Returns (y, aux_vec) where aux_vec is (B_local,) so the caller can
    mean-reduce the load-balance loss across data shards.
    """
    m = cfg.moe
    bl, s, d = x_blk.shape
    n = bl * s
    k = m.experts_per_token
    e_local = w_up.shape[0]
    tokens = x_blk.reshape(n, d)
    gates, idx, aux = _route(cfg, router, tokens)
    col = _axis_index_flat(cfg.ep_axis)
    lo = col * e_local

    flat_e = idx.reshape(-1)                             # (n*k,)
    local_e = flat_e - lo
    mine = (local_e >= 0) & (local_e < e_local)
    # Sort my copies first, grouped by local expert; foreign copies sink
    # into a trailing bucket beyond every expert's capacity window.
    sort_key = jnp.where(mine, local_e, e_local)
    order = jnp.argsort(sort_key)

    # Fixed per-expert capacity -> (E_local, cap_e, D) blocks. Minimum 8
    # rows keeps the expert GEMM a real (MXU-shaped) dot at decode batch
    # sizes (m=1 matvecs lower to f32 elementwise fusions on CPU and
    # would inflate the roofline's memory term; on TPU they underfill
    # the MXU anyway).
    cap_e = int(np.ceil(n * k / m.num_experts * cfg.ep_capacity_factor))
    cap_e = max(min(cap_e, n * k), min(8, n * k))
    counts = jnp.bincount(sort_key, length=e_local + 1)[:e_local]
    offsets = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]]
    )
    slot = jnp.arange(cap_e)[None, :]                    # (1, cap_e)
    valid = slot < counts[:, None]                       # (E_local, cap_e)
    pos = jnp.minimum(offsets[:, None] + slot, n * k - 1)
    take = jnp.take(order, pos.reshape(-1))              # sorted-row ids
    token_of = take // k

    xb = jnp.take(tokens, token_of, axis=0).reshape(e_local, cap_e, d)
    xb = jnp.where(valid[..., None], xb, 0)
    out = _expert_ffn_blocked(cfg, w_gate, w_up, w_down, xb)

    gate_of = jnp.take(gates.reshape(-1), take)
    gate_of = jnp.where(valid.reshape(-1), gate_of, 0.0)

    y = jnp.zeros((n, d), dtype=out.dtype)
    y = y.at[token_of].add(
        out.reshape(-1, d) * gate_of[:, None].astype(out.dtype)
    )
    y = jax.lax.psum(y, cfg.ep_axis)
    aux_g = jax.lax.pmean(aux, axis_name=axis_names)
    return y.reshape(bl, s, d).astype(x_blk.dtype), jnp.full((bl,), aux_g)


def _moe_local_body_a2a(cfg: ModelConfig, axis_names, router, w_gate, w_up, w_down, x_blk):
    """All-to-all expert dispatch (§Perf iteration 4, ``ep_combine='a2a'``).

    x_blk: (B_local, S_local, D) — tokens sharded over BOTH the batch
    axes and the ep axis (sequence-sharded). Each device routes only its
    own chunk, exchanges token copies with the owning expert columns via
    two ``all_to_all``s, and writes back its chunk — no token
    replication, no psum over the ep axis. Collective bytes per layer
    drop from O(replicate + psum) = 3+ full activations to
    ~2 x k x cf / cols of one activation.
    """
    m = cfg.moe
    bl, s_loc, d = x_blk.shape
    n = bl * s_loc
    k = m.experts_per_token
    e_local = w_up.shape[0]
    cols = m.num_experts // e_local
    tokens = x_blk.reshape(n, d)

    gates, idx, aux = _route(cfg, router, tokens)
    flat_e = idx.reshape(-1)                       # (n*k,) global expert id
    dest = flat_e // e_local                       # owning column

    # ---- outbound: pack copies into per-destination capacity slots ----
    order = jnp.argsort(dest)
    cap_s = int(np.ceil(n * k / cols * cfg.ep_capacity_factor))
    cap_s = min(cap_s, n * k)
    counts_d = jnp.bincount(dest, length=cols)
    offs_d = jnp.concatenate(
        [jnp.zeros((1,), counts_d.dtype), jnp.cumsum(counts_d)[:-1]]
    )
    slot = jnp.arange(cap_s)[None, :]
    valid_s = slot < counts_d[:, None]             # (cols, cap_s)
    pos = jnp.minimum(offs_d[:, None] + slot, n * k - 1)
    take = jnp.take(order, pos.reshape(-1))        # copy ids, (cols*cap_s,)

    send_x = jnp.take(tokens, take // k, axis=0).reshape(cols, cap_s, d)
    send_x = jnp.where(valid_s[..., None], send_x, 0)
    send_le = jnp.where(
        valid_s, jnp.take(flat_e, take).reshape(cols, cap_s) % e_local, e_local
    ).astype(jnp.int32)                            # e_local = invalid marker
    send_gate = jnp.where(
        valid_s, jnp.take(gates.reshape(-1), take).reshape(cols, cap_s), 0.0
    )

    a2a = lambda v: jax.lax.all_to_all(
        v, cfg.ep_axis, split_axis=0, concat_axis=0, tiled=True
    )
    recv_x = a2a(send_x)                           # (cols, cap_s, d) for MY experts
    recv_le = a2a(send_le)
    recv_valid = recv_le < e_local

    # ---- local expert compute over fixed-capacity blocks --------------
    r = cols * cap_s
    rle = jnp.where(recv_valid, recv_le, e_local).reshape(r)
    order2 = jnp.argsort(rle)
    cap_e = int(np.ceil(r / e_local * cfg.ep_capacity_factor))
    cap_e = max(min(cap_e, r), min(8, r))
    counts_e = jnp.bincount(rle, length=e_local + 1)[:e_local]
    offs_e = jnp.concatenate(
        [jnp.zeros((1,), counts_e.dtype), jnp.cumsum(counts_e)[:-1]]
    )
    slot_e = jnp.arange(cap_e)[None, :]
    valid_e = slot_e < counts_e[:, None]
    pos_e = jnp.minimum(offs_e[:, None] + slot_e, r - 1)
    take2 = jnp.take(order2, pos_e.reshape(-1))    # recv row ids

    xb = jnp.take(recv_x.reshape(r, d), take2, axis=0).reshape(e_local, cap_e, d)
    xb = jnp.where(valid_e[..., None], xb, 0)
    out_b = _expert_ffn_blocked(cfg, w_gate, w_up, w_down, xb)

    out_recv = jnp.zeros((r, d), out_b.dtype)
    out_recv = out_recv.at[take2].add(
        out_b.reshape(-1, d) * valid_e.reshape(-1, 1)
    )

    # ---- return trip + combine ----------------------------------------
    back = a2a(out_recv.reshape(cols, cap_s, d))   # rows at original slots
    gate_w = send_gate.reshape(-1)[:, None].astype(back.dtype)
    y = jnp.zeros((n, d), back.dtype)
    y = y.at[take // k].add(back.reshape(-1, d) * gate_w)

    aux_g = jax.lax.pmean(aux, axis_name=axis_names)
    aux_mat = jnp.full((bl, s_loc), aux_g, jnp.float32)
    return y.reshape(bl, s_loc, d).astype(x_blk.dtype), aux_mat


# The concrete mesh shard_map runs over; set by the launcher before
# tracing (jax.shard_map inside jit needs a concrete Mesh, and frozen
# ModelConfig cannot carry one).
_EP_MESH = None


def set_ep_mesh(mesh) -> None:
    global _EP_MESH
    _EP_MESH = mesh


def moe_forward_ep(
    cfg: ModelConfig, params: dict, x: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE: experts over ``cfg.ep_axis``; activations
    sharded over the batch axes and replicated over the ep axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _EP_MESH
    if mesh is None:
        raise RuntimeError(
            "cfg.ep_axis set but no EP mesh registered; call "
            "repro.models.moe.set_ep_mesh(mesh) first"
        )
    ep_axes = (
        (cfg.ep_axis,) if isinstance(cfg.ep_axis, str) else tuple(cfg.ep_axis)
    )
    batch_axes = tuple(
        a for a in ("pod", "data") if a in mesh.shape and a not in ep_axes
    )
    ba = batch_axes if batch_axes else None
    ep_size = 1
    for a in ep_axes:
        ep_size *= mesh.shape.get(a, 1)
    use_a2a = cfg.ep_combine == "a2a" and x.shape[1] % max(ep_size, 1) == 0
    if use_a2a:
        bspec = P(ba, cfg.ep_axis, None)         # sequence-sharded tokens
        aux_spec = P(ba, cfg.ep_axis)
        local_body = _moe_local_body_a2a
    else:
        bspec = P(ba, None, None)                # tokens replicated over ep
        aux_spec = P(ba)
        local_body = _moe_local_body
    axis_names = tuple(mesh.axis_names)
    body = jax.shard_map(
        lambda r, wg, wu, wd, xb: local_body(cfg, axis_names, r, wg, wu, wd, xb),
        mesh=mesh,
        in_specs=(
            P(None, None),                       # router (replicated)
            P(cfg.ep_axis, None, None),          # expert shards
            P(cfg.ep_axis, None, None),
            P(cfg.ep_axis, None, None),
            bspec,                               # tokens
        ),
        out_specs=(bspec, aux_spec),
        check_vma=False,
    )
    y, aux_vec = body(
        params["router"], params["w_gate"], params["w_up"], params["w_down"], x
    )
    if cfg.moe.num_shared_experts:
        y = y + mlp_forward(cfg, params["shared"], x)
    return y, aux_vec.reshape(-1)[0]


def moe_apply(cfg: ModelConfig, params: dict, x: jax.Array):
    """Dispatch: expert-parallel under a mesh, ragged single-device
    otherwise."""
    if cfg.ep_axis:
        return moe_forward_ep(cfg, params, x)
    return moe_forward(cfg, params, x)
