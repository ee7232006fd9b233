"""Fused per-layer kernels: one Pallas launch per GNN layer.

The unfused serve path emits several device dispatches per layer — the dense
binary transform, one BSpMM ``pallas_call`` per adjacency (two for the
sharded intra+halo split), BN and the activation as separate XLA ops. The
bit-tensor-core study (Li & Su; PAPERS.md) shows the packed-bit-ops ceiling
sits far above what separate small launches reach, so this module emits the
WHOLE layer — BN -> binary transform -> BSpMM aggregation -> combine /
activation — as ONE ``pallas_call``:

  * :func:`fused_call` — the generic runner: evaluates an arbitrary jnp
    layer function over whole-array operands inside a single kernel body
    (no grid: one launch, one trace). Model weights enter as closure
    constants; every traced value (activations, BN stats, FRDC fields)
    is a kernel operand.
  * :func:`agg_fp` / :func:`agg_counts` / :func:`agg_fp_pair` — the
    aggregation stages expressed as VALUE-level group walks that a kernel
    body can trace (a ``pallas_call`` cannot nest another one). They walk
    ``grp_ptr`` row ranges and accumulate groups in EXACTLY the kernel
    order with the kernel's own Step ③-⑤ helpers — sequential per
    tile-row, one ``(TILE, 64) @ (64, lanes)`` window dot or popc per
    group — so fused results are BITWISE identical to the unfused kernels,
    not merely close. Scale handling
    mirrors ``ops._serve_fp_backend`` / ``ops.serve_fp_pair`` (col scales
    folded into the operand, the shared row scale applied ONCE after the
    intra+halo add).

Calls are counted in :data:`KERNEL_CALLS` at trace time — the
launches-per-layer regression metric (fused layer == 1) benches and tests
key on.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.frdc import FRDCMatrix, TILE

from . import resolve_interpret

from .bspmm_kernel import (SLAB, WORD, _lane_pad, bit_transpose,
                           coarse_words, group_mask, trinary)

# trace-time counters: [fused kernel launches, fused layers' aggregation
# calls folded into them] — reset/read by tests and the launch benches.
KERNEL_CALLS = {"fused": 0, "fused_aggs": 0}


def reset_counters() -> None:
    KERNEL_CALLS["fused"] = 0
    KERNEL_CALLS["fused_aggs"] = 0


# ---------------------------------------------------------------------------
# Value-level aggregation (kernel-body traceable, kernel-order bitwise)
# ---------------------------------------------------------------------------

def _pad_rows(x: jax.Array) -> jax.Array:
    pad = (-x.shape[0]) % SLAB
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _group(adj: FRDCMatrix, x: jax.Array, g):
    """Group ``g``'s coarse adjacency words and its GATHER gathered window
    rows — the kernel's Step ②/③ on values."""
    cols = adj.col_idx[g].astype(jnp.int32)
    rows = ((cols >> 1)[:, None] * SLAB
            + jnp.arange(SLAB, dtype=jnp.int32)).reshape(-1)
    return coarse_words(adj.tiles[g], cols), x[rows]


def _walk_rows(adj: FRDCMatrix, width: int, dtype, g_body):
    """Per tile-row, accumulate ``g_body`` over its ``grp_ptr`` group range
    sequentially — the kernels' order."""
    n_tr = adj.n_tile_rows

    def row_body(r, out):
        acc = jax.lax.fori_loop(adj.grp_ptr[r], adj.grp_ptr[r + 1], g_body,
                                jnp.zeros((TILE, width), dtype))
        return jax.lax.dynamic_update_slice(out, acc, (r * TILE, 0))

    return jax.lax.fori_loop(0, n_tr, row_body,
                             jnp.zeros((n_tr * TILE, width), dtype))


def _lanes(x: jax.Array) -> jax.Array:
    """Zero-pad features to the kernels' lane-padded width (same dot
    shapes, so the same bits)."""
    return jnp.pad(x, ((0, 0), (0, _lane_pad(x.shape[1]) - x.shape[1])))


def _walk_fp(adj: FRDCMatrix, x: jax.Array) -> jax.Array:
    """Raw fp aggregation in kernel order: one (TILE, GATHER) mask dot per
    group, so results are bitwise identical to the Pallas kernel."""
    xl = _lanes(x)

    def g_body(g, acc):
        words, xg = _group(adj, xl, g)
        return acc + jax.lax.dot(group_mask(words, x.dtype), xg,
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=acc.dtype)

    return _walk_rows(adj, xl.shape[1], x.dtype, g_body)[:, :x.shape[1]]


def _walk_counts(adj: FRDCMatrix, xp: jax.Array, trinary_s2: bool
                 ) -> jax.Array:
    """Raw trinary popc counts in kernel order (integer — exact)."""
    wf = xp.shape[1]
    xi = jax.lax.bitcast_convert_type(xp, jnp.int32)

    def g_body(g, acc):
        words, xg = _group(adj, xi, g)
        bt = bit_transpose(
            lambda c: jax.lax.dynamic_slice_in_dim(xg, c * SLAB, SLAB), wf)
        rows = [trinary(words[i], words[TILE + i], bt, trinary_s2)
                .T.reshape(-1) for i in range(TILE)]
        return acc + jnp.stack(rows)

    return _walk_rows(adj, wf * WORD, jnp.int32, g_body)


def agg_fp(adj: FRDCMatrix, x: jax.Array, block_shape=None) -> jax.Array:
    """In-kernel twin of ``ops._serve_fp_backend``: col scales folded into
    the operand, raw kernel-order aggregation, crop, row scale."""
    del block_shape  # math-neutral inside one kernel body
    KERNEL_CALLS["fused_aggs"] += 1
    xin = x
    if adj.col_scale is not None:
        xin = xin * adj.col_scale[:, None].astype(x.dtype)
    out = _walk_fp(adj, _pad_rows(xin))[: adj.n_rows]
    if adj.row_scale is not None:
        out = out * adj.row_scale[:, None].astype(out.dtype)
    return out


def agg_counts(adj: FRDCMatrix, x_packed: jax.Array,
               trinary_mode: str = "s3_two_popc",
               block_shape=None) -> jax.Array:
    """In-kernel twin of ``ops._serve_bits_backend`` / ``serve_counts``:
    raw trinary counts, cropped to real rows (integer — exact across any
    intra/halo split)."""
    del block_shape
    KERNEL_CALLS["fused_aggs"] += 1
    xp = _pad_rows(x_packed)
    return _walk_counts(adj, xp, trinary_mode == "s2_and_andnot")[
        : adj.n_rows]


def agg_fp_pair(intra: FRDCMatrix, halo: FRDCMatrix, x_local: jax.Array,
                x_remote: jax.Array) -> jax.Array:
    """In-kernel twin of ``ops.serve_fp_pair``: the shared row scale is
    applied ONCE after the intra+halo add (the factored form XLA would
    rewrite to anyway — keeping host/SPMD/fused bit-identical)."""
    y = agg_fp(intra._replace(row_scale=None), x_local) \
        + agg_fp(halo._replace(row_scale=None), x_remote)
    if intra.row_scale is not None:
        y = y * intra.row_scale[:, None].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# The fused-layer runner
# ---------------------------------------------------------------------------

def fused_call(fn, *args, interpret: Optional[bool] = None):
    """Evaluate ``fn(*args)`` as ONE Pallas kernel over whole-array operands.

    ``args`` is a pytree whose array leaves become kernel operands (``None``
    subtrees pass through); ``fn`` must return an array or a pytree of
    arrays. The kernel has no grid — a single launch computes the whole
    layer, with the layer's aggregation expressed through the value-level
    walks above (a kernel body cannot nest another ``pallas_call``).

    Model weights captured in ``fn``'s closure are hoisted into kernel
    operands too (``jax.closure_convert`` — Pallas forbids captured array
    constants); whole-array operands mean the layer must fit the serving
    bucket sizes this repo pads to (it does — the same arrays already live
    in VMEM across the unfused kernels' grid steps).
    """
    leaves, treedef = jax.tree.flatten(args)
    arrs = [jnp.asarray(l) for l in leaves]

    def call(*flat):
        return fn(*jax.tree.unflatten(treedef, flat))

    out_sds = jax.eval_shape(call, *arrs)
    out_leaves, out_tree = jax.tree.flatten(out_sds)
    # Hoist EVERY captured constant (weights, iotas) into an operand —
    # Pallas forbids captured array constants, and jax.closure_convert
    # only lifts differentiable ones. The kernel replays the jaxpr.
    closed = jax.make_jaxpr(call)(*arrs)
    consts = [jnp.asarray(c) for c in closed.consts]
    operands = arrs + consts
    KERNEL_CALLS["fused"] += 1

    def kernel(*refs):
        ins = [r[...] for r in refs[:len(operands)]]
        outs = jax.core.eval_jaxpr(closed.jaxpr, ins[len(arrs):],
                                   *ins[:len(arrs)])
        for r, o in zip(refs[len(operands):], outs):
            r[...] = o

    out = pl.pallas_call(
        kernel,
        out_shape=tuple(
            jax.ShapeDtypeStruct(s.shape, s.dtype) for s in out_leaves),
        interpret=resolve_interpret(interpret),
    )(*operands)
    return jax.tree.unflatten(out_tree, list(out))
