"""Plain reference of the binary GraphSAGE network as served: two layers
with mean aggregation over the 0/1 adjacency, each
``B(z) W_self + D^-1 A (B(z) W_agg)`` with z the batch-normed input and B
the row-scaled sign, the first through a relu; no fully connected layer.

The aggregation runs over slices of the edge list, so that a graph of
tens of millions of edges fits the chip: gathering every edge's row at
once is (E, F) floats, 77 GB for Reddit's 74.9M edges at width 256.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ._common import batch_norm, binary_dense

# edges per aggregation step: a (EDGE_CHUNK, F) gather, 1 GiB at width 256
EDGE_CHUNK = 1 << 20


def mean_aggregate(edges, y, n, chunk=EDGE_CHUNK):
    """out[r] = mean over edges (r, c) of y[c] (0 where r has no edge),
    summed over slices of ``chunk`` edges."""
    rows, cols = edges
    e = rows.shape[0]
    steps = max(-(-e // chunk), 1)
    pad = steps * chunk - e
    # padded edges land on row n, one past the graph, and are cut off
    rows = jnp.pad(rows, (0, pad), constant_values=n).reshape(steps, chunk)
    cols = jnp.pad(cols, (0, pad)).reshape(steps, chunk)

    def step(acc, rc):
        r, c = rc
        return acc.at[r].add(y[c]), None

    acc = jnp.zeros((n + 1, y.shape[1]), y.dtype)
    acc, _ = jax.lax.scan(step, acc, (rows, cols))
    deg = jnp.zeros(n + 1, jnp.int32).at[rows.reshape(-1)].add(1)
    return acc[:n] / jnp.maximum(deg[:n], 1).astype(y.dtype)[:, None]


def _layers(params, x, edges, n, chunk=EDGE_CHUNK):
    w1_self, w1_agg, w2_self, w2_agg = params
    z1 = batch_norm(x)
    h = jax.nn.relu(binary_dense(z1, w1_self)
                    + mean_aggregate(edges, binary_dense(z1, w1_agg), n,
                                     chunk))
    z2 = batch_norm(h)
    out = binary_dense(z2, w2_self) + mean_aggregate(
        edges, binary_dense(z2, w2_agg), n, chunk)
    return out, z2


def forward(params, x, edges, n):
    """Logits (n, classes) of every node. ``params``: (w1_self, w1_agg,
    w2_self, w2_agg)."""
    return _layers(params, x, edges, n)[0]


def fragile(params, x, edges, n, eps):
    """Nodes whose logits can move under float32 rounding alone: a value
    the network binarises at layer 2 (a batch-normed activation) lies
    within ``eps`` of zero at the node or at a node it aggregates from. A
    different reduction order of a batch-norm statistic can flip such a
    sign, and the flip moves the logits by a binary step (over the degree
    where it comes through the mean). The features entering layer 1 are
    0/1 and never land near zero; a unit that is zero at every node stays
    exactly zero in any order."""
    _, z2 = _layers(params, x, edges, n)
    dead = jnp.all(z2 == 0, axis=0, keepdims=True)
    near = jnp.any((jnp.abs(z2) < eps) & ~dead, axis=1)
    rows, cols = edges
    from_near = jax.ops.segment_max(near[cols].astype(jnp.int32), rows,
                                    num_segments=n) > 0
    return near | from_near
