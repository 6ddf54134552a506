"""Graph attention network (GAT, Veličković et al., ICLR 2018) in pure JAX.

L ``GATConv`` layers over the sampler's tree-form blocks, configured as
OGB's "GAT w/NS" for ogbn-products (PyG ``examples/ogbn_products_gat.py``):
``heads`` heads of ``hidden_dim`` channels, concatenated, in every layer
but the last, which averages its heads into ``num_classes`` channels.
For a target ``i`` whose sources are its sampled children and itself,
each layer computes, per head ``h``::

    e_ij  = LeakyReLU_0.2(a_src · W_h x_j + a_dst · W_h x_i)
    α_ij  = softmax_j(e_ij)                 (over the children and i)
    out_i = concat_h | mean_h (Σ_j α_ij W_h x_j) + b + Lin(x_i)

with one ``W`` shared by sources and targets and ``Lin`` a linear skip
with bias; ELU follows every layer but the last. The softmax subtracts
each target's largest logit.

Each layer computes those sums in the reordered order, which is exact:
the scores need only ``a · W_h x = x · (W_h^T a)``, and the weighted sum
of projected rows is the projection of the weighted sum of raw rows,
``Σ_j α_ij W_h x_j = W_h (Σ_j α_ij x_j)``. So ``W_h^T a_src`` and
``W_h^T a_dst`` are folded into one ``(D, heads)`` matrix each, every
raw row is scored with them, the raw rows are summed per head with the
softmax weights, and only each target's sum, ``(n, heads, D)``, is
projected. The published order projects every row, the deepest hop
(``B f1 ... fL`` rows) included, into ``heads x C`` channels; this one
never widens a source row, and does fewer operations unless a head has
only a few channels (``bench/models/gat.py``'s ``layer_flops`` counts
both).

The blocks are ``(B,), (B, f1), ..., (B, f1, ..., fL)``: the seeds and
each hop's ids, dense features or :class:`Rows` of a device table. Each
hop's rows are laid out with their id axes reversed
(:meth:`Rows.fanout_major`): hop ``k`` as ``(n_k, F)`` is then, reshaped
to ``(f_k, n_{k-1}, F)``, the children of hop ``k-1``'s rows in their
own order, so one gather gives every hop both as targets and as sources.
Layer ``l`` turns hops ``0..L-l+1`` into hops ``0..L-l``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .sage import Rows


class GatLayer(NamedTuple):
    w: jax.Array        # (D_in, heads * C), shared by sources and targets
    att_src: jax.Array  # (heads, C)
    att_dst: jax.Array  # (heads, C)
    bias: jax.Array     # (D_out,)
    skip_w: jax.Array   # (D_in, D_out)
    skip_b: jax.Array   # (D_out,)


class GatParams(NamedTuple):
    layers: tuple[GatLayer, ...]


def init_gat(
    key: jax.Array,
    feature_dim: int,
    hidden_dim: int,
    num_classes: int,
    heads: int = 4,
    num_layers: int = 3,
) -> GatParams:
    """Glorot-normal matrices and attention vectors, zero biases."""

    def glorot(k, a, b):
        return jax.random.normal(k, (a, b), dtype=jnp.float32) * (
            2.0 / (a + b)
        ) ** 0.5

    layers = []
    d_in = feature_dim
    for i, k in enumerate(jax.random.split(key, num_layers)):
        last = i == num_layers - 1
        c = num_classes if last else hidden_dim
        d_out = c if last else heads * c
        kw, ks, kd, kk = jax.random.split(k, 4)
        layers.append(
            GatLayer(
                w=glorot(kw, d_in, heads * c),
                att_src=glorot(ks, heads, c),
                att_dst=glorot(kd, heads, c),
                bias=jnp.zeros((d_out,), jnp.float32),
                skip_w=glorot(kk, d_in, d_out),
                skip_b=jnp.zeros((d_out,), jnp.float32),
            )
        )
        d_in = d_out
    return GatParams(tuple(layers))


def _hop_rows(x) -> jax.Array:
    """A hop's rows, ``(n, F)``, with its id axes reversed."""
    if isinstance(x, Rows):
        rows = x.fanout_major()
    else:
        feat = x.ndim - 1
        rows = jnp.transpose(x, (*range(feat - 1, -1, -1), feat))
    return rows.reshape(-1, rows.shape[-1])


def _layer(layer: GatLayer, h: list, last: bool) -> list:
    """One ``GATConv`` plus skip over hops ``h[0..K]``: the outputs of
    hops ``0..K-1``, each hop's children being the next hop's rows."""
    d = h[0].shape[-1]
    heads, c = layer.att_src.shape
    wh = layer.w.reshape(d, heads, c)
    u_src = jnp.einsum("dhc,hc->dh", wh, layer.att_src)             # (D, heads)
    u_dst = jnp.einsum("dhc,hc->dh", wh, layer.att_dst)
    src = [x @ u_src for x in h]                                    # (n, heads)
    out = []
    for k in range(len(h) - 1):
        n = len(h[k])
        dst = h[k] @ u_dst                                          # (n, heads)
        e_self = jax.nn.leaky_relu(src[k] + dst, 0.2)
        e_kids = jax.nn.leaky_relu(src[k + 1].reshape(-1, n, heads) + dst, 0.2)
        top = jnp.maximum(e_self, jnp.max(e_kids, axis=0))
        a_self = jnp.exp(e_self - top)
        a_kids = jnp.exp(e_kids - top)                              # (f, n, heads)
        denom = a_self + jnp.sum(a_kids, axis=0)
        a_self, a_kids = a_self / denom, a_kids / denom
        kids = h[k + 1].reshape(-1, n, d)                           # (f, n, D)
        r = a_self[..., None] * h[k][:, None] + jnp.einsum("fnh,fnd->nhd", a_kids, kids)
        agg = jnp.einsum("nhd,dhc->nhc", r, wh)                     # (n, heads, C)
        agg = jnp.mean(agg, axis=1) if last else agg.reshape(n, heads * c)
        out.append(agg + layer.bias + h[k] @ layer.skip_w + layer.skip_b)
    return out


def gat_forward(params: GatParams, blocks) -> jax.Array:
    """Logits ``(B, num_classes)`` of the seeds; ``blocks`` holds the
    seed block and one block per layer, any of them :class:`Rows`."""
    layers = params.layers
    if len(blocks) != len(layers) + 1:
        raise ValueError(
            f"{len(layers)} layers read {len(layers) + 1} blocks, got {len(blocks)}"
        )
    h = [_hop_rows(x) for x in blocks]
    for i, layer in enumerate(layers):
        last = i == len(layers) - 1
        h = _layer(layer, h, last)
        if not last:
            h = [jax.nn.elu(x) for x in h]
    return h[0]


def gat_loss(params: GatParams, blocks, labels: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(gat_forward(params, blocks), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


@jax.jit
def gat_grads(params: GatParams, blocks, labels):
    """One trainer's loss and gradient over its minibatch."""
    return jax.value_and_grad(gat_loss)(params, blocks, labels)


@jax.jit
def gat_accuracy(params: GatParams, blocks, labels):
    logits = gat_forward(params, blocks)
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
