"""Plain reference of the binary GraphSAINT network as served: two
GraphConv layers with sum aggregation over the 0/1 adjacency, each
``relu(B(z) W_self + A (B(z) W_agg))`` with z the batch-normed input and
B the row-scaled sign, then a binary fully connected layer."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ._common import aggregate, batch_norm, binary_dense


def _layers(params, x, edges, n):
    w1_self, w1_agg, w2_self, w2_agg, w_fc = params
    h = x
    zs = []
    for w_self, w_agg in ((w1_self, w1_agg), (w2_self, w2_agg)):
        z = batch_norm(h)
        zs.append(z)
        h = jax.nn.relu(binary_dense(z, w_self)
                        + aggregate(edges, binary_dense(z, w_agg), n))
    z = batch_norm(h)
    zs.append(z)
    return binary_dense(z, w_fc), zs


def forward(params, x, edges, n):
    """Logits (n, classes) of every node. ``params``: (w1_self, w1_agg,
    w2_self, w2_agg, w_fc)."""
    return _layers(params, x, edges, n)[0]


def fragile(params, x, edges, n, eps):
    """Nodes whose logits can move under float32 rounding alone: a value
    the network binarises (a batch-normed activation of layers 2 and 3)
    lies within ``eps`` of zero at the node, or, for layer 2, at a node it
    aggregates from. A different reduction order of a batch-norm statistic
    can flip such a sign, and the flip moves the logits by a binary step.
    The features entering layer 1 are 0/1 and never land near zero; a
    unit that is zero at every node stays exactly zero in any order."""
    _, (_, z2, z3) = _layers(params, x, edges, n)
    near2, near3 = (jnp.any(_near_zero(z, eps), axis=1) for z in (z2, z3))
    rows, cols = edges
    from_near2 = jax.ops.segment_max(near2[cols].astype(jnp.int32), rows,
                                     num_segments=n) > 0
    return near2 | near3 | from_near2


def _near_zero(z, eps):
    dead = jnp.all(z == 0, axis=0, keepdims=True)
    return (jnp.abs(z) < eps) & ~dead
