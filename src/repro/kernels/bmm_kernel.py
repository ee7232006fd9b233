"""Pallas TPU kernel: dense binary (XNOR-popc) matmul — BMM.BB? compute core.

TPU adaptation of BSTC-style bit-GEMM (paper §3.3 references [28,31]):
operands are bit-packed along the contraction axis K into uint32 lanes; each
grid cell owns a (TM, TN) output tile held in VREGs/VMEM and marches over the
packed words with XOR+popcount on the VPU (there is no 1-bit MXU mode).

Layout: A (M, Wk) uint32, B (N, Wk) uint32 — B is the *transposed* weight
(packed along K), matching ``core.bmm.quantize_weight``. Output (M, N) int32
sign-count, or fused-binarized (M, N/32) uint32 when ``binarize=True``
(the paper's Step ⑥ fused bit-tensor store).

Block sizes default to (128, 128): MXU/VPU-aligned, VMEM per step =
TM*Wk*4 + TN*Wk*4 + TM*TN*4 bytes (< 1.5 MB for K=20480).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

WORD = 32


def _xnor_popc_tile(a, b):
    """(TM, Wk) x (TN, Wk) -> (TM, TN) popcount(XOR) accumulated over words."""
    tm, wk = a.shape
    tn = b.shape[0]

    def body(w, acc):
        aw = jax.lax.dynamic_slice(a, (0, w), (tm, 1))        # (TM, 1)
        bw = jax.lax.dynamic_slice(b, (0, w), (tn, 1))        # (TN, 1)
        x = jax.lax.population_count(aw ^ bw.reshape(1, tn))  # (TM, TN)
        return acc + x.astype(jnp.int32)

    acc = jnp.zeros((tm, tn), jnp.int32)
    return jax.lax.fori_loop(0, wk, body, acc)


def _bmm_xnor_kernel(a_ref, b_ref, o_ref, *, n_bits: int):
    acc = _xnor_popc_tile(a_ref[...], b_ref[...])
    o_ref[...] = n_bits - 2 * acc


def _bmm_xnor_bin_kernel(a_ref, b_ref, o_ref, *, n_bits: int):
    """Fused Step ⑥: binarize the sign-counts and pack to uint32 in-kernel."""
    acc = _xnor_popc_tile(a_ref[...], b_ref[...])
    signs = (n_bits - 2 * acc) >= 0                          # (TM, TN) bool
    tm, tn = signs.shape
    grouped = signs.reshape(tm, tn // WORD, WORD).astype(jnp.uint32)
    weights = jnp.left_shift(jnp.uint32(1), jnp.arange(WORD, dtype=jnp.uint32))
    o_ref[...] = jnp.sum(grouped * weights, axis=-1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("n_bits", "binarize", "block_m",
                                             "block_n", "interpret"))
def bmm_xnor(a_packed: jax.Array, b_packed: jax.Array, n_bits: int,
             binarize: bool = False, block_m: int = 128, block_n: int = 128,
             interpret: Optional[bool] = None) -> jax.Array:
    """sign(X) @ sign(W) on packed operands.

    a_packed: (M, Wk) uint32; b_packed: (N, Wk) uint32 (weight transposed).
    Returns (M, N) int32, or (M, N/32) uint32 bits when ``binarize``.
    M, N are padded up to block multiples internally and cropped.
    """
    m, wk = a_packed.shape
    n = b_packed.shape[0]
    assert b_packed.shape[1] == wk
    bm, bn = min(block_m, _ceil_mult(m, 8)), min(block_n, _ceil_mult(n, WORD))
    mp, np_ = _ceil_mult(m, bm), _ceil_mult(n, bn)
    a_p = jnp.pad(a_packed, ((0, mp - m), (0, 0)))
    b_p = jnp.pad(b_packed, ((0, np_ - n), (0, 0)))

    if binarize:
        kernel = functools.partial(_bmm_xnor_bin_kernel, n_bits=n_bits)
        out_shape = jax.ShapeDtypeStruct((mp, np_ // WORD), jnp.uint32)
        out_spec = pl.BlockSpec((bm, bn // WORD), lambda i, j: (i, j))
    else:
        kernel = functools.partial(_bmm_xnor_kernel, n_bits=n_bits)
        out_shape = jax.ShapeDtypeStruct((mp, np_), jnp.int32)
        out_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))

    out = pl.pallas_call(
        kernel,
        grid=(mp // bm, np_ // bn),
        in_specs=[pl.BlockSpec((bm, wk), lambda i, j: (i, 0)),
                  pl.BlockSpec((bn, wk), lambda i, j: (j, 0))],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(a_p, b_p)
    if not binarize:
        return out[:m, :n]
    # Crop to the logical word count and ZERO any tail bits belonging to
    # padded columns — chained popc consumers rely on 0-padding (pad-safety
    # invariant of core.bitops).
    wn = (n + WORD - 1) // WORD
    out = out[:m, :wn]
    tail = n % WORD
    if tail:
        mask = jnp.uint32((1 << tail) - 1)
        out = out.at[:, -1].set(out[:, -1] & mask)
    return out


def _ceil_mult(x: int, m: int) -> int:
    return -(-x // m) * m
