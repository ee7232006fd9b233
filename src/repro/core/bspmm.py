"""BSpMM — binary sparse(adjacency) x dense matmul over FRDC (paper §3.3).

Eight variants, named ``BSpMM.<X><A><O>``: X = dense-operand precision (the
activations), A = adjacency (always binary bits; ``weighted`` — i.e. carrying
the §3.1.2 factorization vectors — doubles the variant count), O = output.

Semantics (out = Adj_eff @ X):
  * FBF / FBB : fp activations; EXACT for factorized adjacencies
                (col scales fold into X rows, row scales fold out — and are
                elided when O==B since they are positive).
  * BBF / BBB : binary ±1 activations via the trinary popc dot-product
                (§3.2.2); per-neighbor scales cannot cross popc, so this is
                the paper's *binary aggregation approximation* — the same one
                behind "Ours (bin)" in Tables 3-5.

The group-wise math here (gather -> coarsen -> bit-transpose -> popc ->
binarize) is the exact algorithm of the Pallas kernel; this module is both
the CPU execution path and the kernel's structural reference.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp

from . import bitops
from .binarize import BinTensor
from .frdc import (FRDCMatrix, GROUP_COLS, TILE, coarsen_groups,
                   group_neighbor_ids)

BSPMM_VARIANTS = ("FBF", "FBB", "BBF", "BBB")
TRINARY_DEFAULT = "s3_two_popc"

# Pluggable execution backends: when set (see kernels.ops.serve_kernels),
# the fp aggregation / trinary-counts stages run through them instead of the
# jnp reference below. The hooks sit at the same semantic level as the
# reference helpers: fp(adj, x) -> (n_rows, F) with scales applied;
# bits(adj, x_packed, trinary_mode) -> (n_rows, Wf*32) int32 counts.
_FP_BACKEND: Optional[Callable] = None
_BITS_BACKEND: Optional[Callable] = None

# The fp aggregation gathers GROUP_COLS rows of x per group; it runs over
# blocks of groups whose gather holds at most this many bytes, so its
# temporaries stay bounded whatever the graph. A block of 4 GiB holds the
# 131,072 groups of a width-256 subgraph bucket whole.
FP_BLOCK_BYTES = 4 << 30
LANES = 128             # a TPU vector row: narrower widths are padded to it


@contextlib.contextmanager
def override_backends(fp: Optional[Callable] = None,
                      bits: Optional[Callable] = None):
    """Route BSpMM stages through alternative implementations (Pallas
    kernels). The override is consulted at call/trace time, so wrapping a
    jit trace bakes the backend into the compiled executable."""
    global _FP_BACKEND, _BITS_BACKEND
    prev = (_FP_BACKEND, _BITS_BACKEND)
    _FP_BACKEND, _BITS_BACKEND = fp, bits
    try:
        yield
    finally:
        _FP_BACKEND, _BITS_BACKEND = prev


def fp_block_groups(width: int, dtype) -> int:
    """Groups per block of the fp aggregation: the largest count whose
    gather, GROUP_COLS rows of ``width`` features per group laid out in
    LANES-wide rows, fits FP_BLOCK_BYTES."""
    lanes = -(-width // LANES) * LANES
    row = GROUP_COLS * lanes * jnp.dtype(dtype).itemsize
    return max(FP_BLOCK_BYTES // row, 1)


def fp_blocks(adj: FRDCMatrix, width: int, dtype=jnp.float32) -> int:
    """Group blocks the fp aggregation of ``adj`` runs over ``width``
    features."""
    return -(-adj.n_groups // fp_block_groups(width, dtype))


def _pad_rows(x: jax.Array, multiple: int) -> jax.Array:
    pad = (-x.shape[0]) % multiple
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x


def _mask_from_words(a_words: jax.Array) -> jax.Array:
    """(G, TILE) uint32 -> (G, TILE, GROUP_COLS) {0,1} lane mask."""
    k = jnp.arange(GROUP_COLS, dtype=jnp.uint32)
    return ((a_words[..., None] >> k) & jnp.uint32(1)).astype(jnp.float32)


def _segment_rows(contrib: jax.Array, adj: FRDCMatrix) -> jax.Array:
    """(G, TILE, F) group contributions -> (n_rows, F) via segment-sum."""
    out = jax.ops.segment_sum(contrib, adj.group_row,
                              num_segments=adj.n_tile_rows)
    out = out.reshape(adj.n_tile_rows * TILE, contrib.shape[-1])
    return out[:adj.n_rows]


def _group_contrib(tiles: jax.Array, col_idx: jax.Array,
                   xp: jax.Array) -> jax.Array:
    """(G, TILE, F) products of G groups: each group's (TILE, GROUP_COLS)
    lane mask times its GROUP_COLS gathered rows of ``xp``."""
    nbr = group_neighbor_ids(col_idx)              # (G, 32)
    xg = xp[nbr]                                   # (G, 32, F)
    mask = _mask_from_words(coarsen_groups(tiles)).astype(xp.dtype)
    # HIGHEST: a TPU's default precision would round xg to bf16; the {0,1}
    # mask product is exact in f32, as in the Pallas kernel
    return jnp.einsum("gkn,gnf->gkf", mask, xg,
                      precision=jax.lax.Precision.HIGHEST)


def _blocked_rows(adj: FRDCMatrix, xp: jax.Array, block: int,
                  n_blocks: int) -> jax.Array:
    """(n_rows, F) sums of every group's contribution, one block of
    ``block`` groups per scan step into one (n_tile_rows, TILE, F)
    accumulator. The last block is padded with empty groups (zero tiles on
    tile-row 0), which add nothing."""
    pad = n_blocks * block - adj.n_groups

    def blocks(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape(n_blocks, block, *a.shape[1:])

    def step(acc, blk):
        tiles, col_idx, group_row = blk
        return acc.at[group_row].add(_group_contrib(tiles, col_idx, xp)), None

    f = xp.shape[-1]
    acc = jnp.zeros((adj.n_tile_rows, TILE, f), xp.dtype)
    acc, _ = jax.lax.scan(step, acc, (blocks(adj.tiles),
                                      blocks(adj.col_idx),
                                      blocks(adj.group_row)))
    return acc.reshape(adj.n_tile_rows * TILE, f)[:adj.n_rows]


def _spmm_fp(adj: FRDCMatrix, x: jax.Array) -> jax.Array:
    """Exact Adj_eff @ X for fp X: gather + masked small-matmul per group.

    The (TILE, GROUP_COLS) x (GROUP_COLS, F) per-group product is the op the
    TPU kernel runs on the MXU (§3.3 'other variants': FB? loads fp rows
    directly, no bit-transpose needed). Groups run in blocks of
    :func:`fp_block_groups`; a matrix that fits one block is one gather and
    one segment-sum, with no loop.
    """
    if adj.col_scale is not None:
        x = x * adj.col_scale[:, None].astype(x.dtype)
    xp = _pad_rows(x, TILE)
    n_blocks = fp_blocks(adj, x.shape[-1], x.dtype)
    if n_blocks == 1:
        out = _segment_rows(_group_contrib(adj.tiles, adj.col_idx, xp), adj)
    else:
        out = _blocked_rows(adj, xp, fp_block_groups(x.shape[-1], x.dtype),
                            n_blocks)
    if adj.row_scale is not None:
        out = out * adj.row_scale[:, None].astype(out.dtype)
    return out


def _spmm_bits(adj: FRDCMatrix, xp: jax.Array,
               trinary_mode: str = TRINARY_DEFAULT) -> jax.Array:
    """Trinary popc aggregation of packed ±1 activations -> (n_rows, F) int32.

    ``xp``: (N_pad_to_TILE, Wf) uint32, features packed along the last axis.
    Per group: gather 32 neighbor rows, bit-transpose 32x32 blocks (Step ④),
    popc against the coarsened adjacency words (Step ⑤).
    """
    nbr = group_neighbor_ids(adj.col_idx)                   # (G, 32)
    bg = xp[nbr]                                            # (G, 32, Wf)
    bt = bitops.bit_transpose_32(jnp.swapaxes(bg, -1, -2))  # (G, Wf, 32)
    a_words = coarsen_groups(adj.tiles)                     # (G, TILE)
    a = a_words[:, :, None, None]                           # (G,T,1,1)
    b = bt[:, None, :, :]                                   # (G,1,Wf,32)
    if trinary_mode == "s3_two_popc":
        c = 2 * bitops.popcount(a & b).astype(jnp.int32) \
            - bitops.popcount(a).astype(jnp.int32)
    elif trinary_mode == "s2_and_andnot":
        c = bitops.popcount(a & b).astype(jnp.int32) \
            - bitops.popcount(a & ~b).astype(jnp.int32)
    else:
        raise ValueError(trinary_mode)
    contrib = c.reshape(c.shape[0], TILE, -1)               # (G, T, F)
    return _segment_rows(contrib, adj)


def bspmm(adj: FRDCMatrix, x: Union[jax.Array, BinTensor], variant: str,
          trinary_mode: str = TRINARY_DEFAULT, out_scale: bool = True):
    """Dispatch a BSpMM variant. ``x`` fp (N,F) for F??, BinTensor for B??."""
    if variant not in BSPMM_VARIANTS:
        raise ValueError(f"unknown BSpMM variant {variant!r}")
    xa, _, op = variant

    if xa == "F":
        full = (_FP_BACKEND or _spmm_fp)(adj, x)
        n_feat = x.shape[-1]
    else:
        assert isinstance(x, BinTensor)
        xp = _pad_rows(x.packed, TILE)
        counts = (_BITS_BACKEND or _spmm_bits)(
            adj, xp, trinary_mode).astype(jnp.float32)
        n_feat = x.n
        counts = counts[:, :n_feat] if counts.shape[-1] > n_feat else counts
        if op == "F":
            # paper's approximation: positive scales re-applied as a mean
            # factor after the bit aggregation ("multiplication with a
            # full-precision factorization vector", §3.1.2).
            full = counts * jnp.mean(x.scale)
            if adj.row_scale is not None:
                full = full * adj.row_scale[:, None]
            if adj.col_scale is not None:
                full = full * jnp.mean(adj.col_scale)
        else:
            full = counts   # every scale is positive -> elided by BIN

    if op == "F":
        return full
    scale = jnp.mean(jnp.abs(full), axis=-1, keepdims=True) if out_scale \
        else jnp.ones((full.shape[0], 1), full.dtype)
    return BinTensor(packed=bitops.sign_bits(full[:, :n_feat], axis=-1),
                     scale=scale, n=n_feat)


def spmm_reference_fp(adj_dense: jax.Array, x: jax.Array) -> jax.Array:
    """Dense oracle: Adj_eff @ X with a decoded dense adjacency."""
    return adj_dense @ x
