"""Pallas TPU kernel: fused binarize-and-pack (the BIN op, paper Fig. 2 ③).

The GPU version ballots a warp's 32 lane predicates into one word; on TPU we
compare a (TM, TF) VMEM tile against 0 and reduce 32-bit lane groups with a
shift/OR (a small reduction along the minor axis — stays in VREGs).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

WORD = 32


def _pack_kernel(x_ref, o_ref):
    signs = (x_ref[...] >= 0)
    tm, tf = signs.shape
    grouped = signs.reshape(tm, tf // WORD, WORD).astype(jnp.uint32)
    weights = jnp.left_shift(jnp.uint32(1), jnp.arange(WORD, dtype=jnp.uint32))
    o_ref[...] = jnp.sum(grouped * weights, axis=-1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("block_m", "block_f", "interpret"))
def binarize_pack(x: jax.Array, block_m: int = 256, block_f: int = 1024,
                  interpret: Optional[bool] = None) -> jax.Array:
    """(M, F) float -> (M, ceil(F/32)) uint32 sign bits (bit=1 iff x>=0).

    Padding columns pack as 0 (pad-safety invariant: padded fp values are
    filled with -1 so their sign bit is 0).
    """
    m, f = x.shape
    bm = min(block_m, _ceil_mult(m, 8))
    bf = min(block_f, _ceil_mult(f, WORD))
    mp, fp_ = _ceil_mult(m, bm), _ceil_mult(f, bf)
    x_p = jnp.pad(x, ((0, mp - m), (0, fp_ - f)), constant_values=-1.0)

    out = pl.pallas_call(
        _pack_kernel,
        grid=(mp // bm, fp_ // bf),
        in_specs=[pl.BlockSpec((bm, bf), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bf // WORD), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, fp_ // WORD), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(x_p)
    return out[:m, : (f + WORD - 1) // WORD]


def _ceil_mult(x: int, m: int) -> int:
    return -(-x // m) * m
