"""Operations shared by the plain references, in straightforward jnp.

Every reference computes from the float parameters and the COO edge list
(row ``edges[0]`` receives from column ``edges[1]``), in the dtype it is
given: float32 under ``jax.default_matmul_precision("highest")`` for the
reference, bfloat16 for the lower-precision control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BN_EPS = 1e-5


def batch_norm(x):
    """Per-feature standardisation over all nodes (the one cross-node
    statistic of a binary GNN forward)."""
    mu = jnp.mean(x, axis=0, keepdims=True)
    sd = jnp.std(x, axis=0, keepdims=True) + BN_EPS
    return (x - mu) / sd


def sign(x):
    """+1 where x >= 0, else -1."""
    return jnp.where(x >= 0, 1, -1).astype(x.dtype)


def binary_weight(w):
    """sign(W) and its per-output-column L1 scale mean_k |W[k, j]|."""
    return sign(w), jnp.mean(jnp.abs(w), axis=0, keepdims=True)


def binary_act(z):
    """sign(z) and its per-row L1 scale mean_f |z[n, f]|."""
    return sign(z), jnp.mean(jnp.abs(z), axis=1, keepdims=True)


def binary_dense(z, w):
    """Binary activations times binary weights, rescaled: the dense
    transform of a binary layer."""
    zs, zscale = binary_act(z)
    ws, wscale = binary_weight(w)
    return (zs @ ws) * zscale * wscale


def aggregate(edges, y, n, row_scale=None, col_scale=None):
    """out[r] = row_scale[r] * sum over edges (r, c) of col_scale[c] * y[c]."""
    rows, cols = edges
    if col_scale is not None:
        y = y * col_scale[:, None]
    out = jax.ops.segment_sum(y[cols], rows, num_segments=n)
    if row_scale is not None:
        out = out * row_scale[:, None]
    return out
