"""Pallas TPU kernel: FRDC binary sparse x dense matmul (paper Algorithm 1).

TPU mapping of the paper's warp algorithm (§3.3.2):

  GPU (per warp)                      TPU (per group of a grid step's walk)
  ------------------------------      ------------------------------------
  ① warp <- one 4x4-tile row          grid step <- a block of tile-rows; it
                                      walks each tile-row's group range
  ② 32 thr load 8 tiles + B rows      8 async DMAs gather, per tile, the
                                      aligned 8-row window that holds its
                                      4-row neighbor slab (HBM -> VMEM)
  ③ shfl bit-concatenate              shift/OR the eight 4x4 tiles into a
                                      (lo, hi) word pair per matrix row over
                                      the 64 gathered window rows
  ④ ballot+brev bit-transpose         shift/OR 32x32 bit transposes of the
                                      gathered activation words (VPU)
  ⑤ popc trinary dot                  popcount AND/ANDNOT on (32, lanes)
  ⑥ ballot+brev binarized store       compare>=0, shift/OR pack, one store
                                      per output block

Two kernels:
  * ``bspmm_bits``  — packed ±1 activations (BSpMM.BB?; Algorithm 1 proper);
  * ``bspmm_fp``    — fp activations (BSpMM.FB?): the gathered (64, F) rows
    hit the MXU via a (4, 64) mask matmul instead of Step ④/⑤.

One grid layout: a 2D block grid, ``block_shape=(rows, feats)``. Each grid
step owns ``rows/TILE`` tile-rows x one feature block, walks its tile-rows'
group ranges off the scalar-prefetched ``grp_ptr`` with DOUBLE-BUFFERED DMA
(the next group's windows stream in while the current one accumulates),
and writes its output block once. ``pad_frdc`` bucket-padding groups live
past ``grp_ptr[-1]`` and are never visited; a tile-row with no groups keeps
zero counts (sign(0) = +1 when binarized).

The layout is shaped by what Mosaic (the TPU kernel compiler) accepts:
  * SMEM is 1 MiB, far less than a graph's FRDC arrays, so Step ③ runs as a
    vectorized XLA pass per call (:func:`coarse_words`) and each group's
    col_idx + coarse words travel as one metadata row that the walk streams
    from HBM through a 3-slot SMEM ring, two groups ahead of use;
  * a DMA may only move whole (8, 128) tiles at a dynamic row offset, so
    activations are laid out as (feature blocks, windows, 8, 128-lane
    padded features) and each tile's slab is fetched with its window;
  * every output block's last two dims equal the output array's, so any
    (rows, feats) tunable is a legal block — the (rows, F) result is
    assembled from the block-major kernel output by one XLA transpose;
  * all bit arithmetic is int32 (packed words are bitcast at the boundary):
    Mosaic has no unsigned reductions, and the pack step sums disjoint bits
    (== OR) in int32;
  * the bit kernels accumulate counts as (32 feature bits, lanes) tiles,
    the layout the bit transpose produces, so no in-kernel relayout.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.frdc import FRDCMatrix, GROUP, TILE

from . import resolve_interpret

WORD = 32
# rows per gathered window: one 32-bit sublane tile, so a window's DMA
# offset is tile-aligned; the window holds the 4-row neighbor slab
SLAB = 2 * TILE
GATHER = GROUP * SLAB         # window rows gathered per group
LANE = 128                    # vreg lane count
META = GROUP + 2 * TILE       # metadata words per group: col_idx, words
GPR = LANE // META            # groups per streamed metadata row
# one sublane tile of output rows per grid step, full feature width
DEFAULT_ROWS = SLAB


# ---------------------------------------------------------------------------
# Block-shape tunable
# ---------------------------------------------------------------------------

class BlockPlan(NamedTuple):
    """Resolved (rows, feats) block tunable of the grid.

    ``rows``: output rows per grid step — a positive multiple of TILE.
    ``feats``: feature width per grid step, or None for the full width.
    """
    rows: int
    feats: Optional[int]


def block_probe(block_shape, f: int, packed_width: bool) -> Optional[str]:
    """Capability probe for a (rows, feats) block shape: ``None`` when the
    grid supports it, else ONE message naming the violation AND the legal
    block-shape space (word alignment, real feature width) — callers get the
    whole picture from any rejection instead of three divergent branches."""
    if block_shape is None:
        return None
    if packed_width:
        feat_space = (f"a positive multiple of the {WORD}-bit word or "
                      f"exactly the real feature width {f} (packed kernels "
                      f"carry word-native features)")
    else:
        feat_space = (f"any positive width (the fp feature dim is "
                      f"zero-padded to the block grid; real width {f})")
    space = (f"legal BSpMM block shapes: rows = a positive multiple of the "
             f"FRDC tile-row height {TILE}; feats = None (full width) or "
             f"{feat_space}")
    rows, feats = block_shape
    rows = int(rows)
    if rows <= 0 or rows % TILE:
        return (f"unsupported bspmm block {tuple(block_shape)!r}: rows "
                f"{rows} is not a positive multiple of {TILE}; {space}")
    if feats is None:
        return None
    feats = int(feats)
    if feats <= 0:
        return (f"unsupported bspmm block {tuple(block_shape)!r}: feats "
                f"{feats} is not positive; {space}")
    if packed_width and feats % WORD and feats != f:
        return (f"unsupported bspmm block {tuple(block_shape)!r}: feats "
                f"{feats} is neither word-aligned nor the real feature "
                f"width; {space}")
    return None


def _block_plan(block_shape, f: int, packed_width: bool) -> BlockPlan:
    """Validate the tunable; None resolves to ``(DEFAULT_ROWS, full)``."""
    reason = block_probe(block_shape, f, packed_width)
    if reason is not None:
        raise ValueError(reason)
    if block_shape is None:
        return BlockPlan(DEFAULT_ROWS, None)
    rows, feats = block_shape
    return BlockPlan(int(rows), None if feats is None else int(feats))


def _resolve_block(block_shape, f: int, packed_width: bool) -> int:
    """Validate the (rows, feats) block-shape tunable and return the padded
    feature width of one grid step's output row-block.

    Packed-word paths (``packed_width``) keep their word-native storage
    width; fp paths zero-pad the feature dimension up to a multiple of the
    block width (exact). Rejections carry the full legal block-shape space —
    see :func:`block_probe`, which is also the non-raising capability test.
    """
    plan = _block_plan(block_shape, f, packed_width)
    if plan.feats is None or packed_width:
        return f
    return -(-f // plan.feats) * plan.feats


# ---------------------------------------------------------------------------
# Kernel pieces
# ---------------------------------------------------------------------------

class _Walk(NamedTuple):
    """The refs a group walk streams through (see :func:`_walk`)."""
    meta_hbm: object    # (G/GPR, 128) int32 group metadata rows (HBM)
    x_hbm: object       # (n_fb, N8/SLAB, SLAB, lanes) activation windows
    meta_ref: object    # (3, 128) int32 SMEM ring of metadata rows
    xg_ref: object      # (2, GATHER, lanes) VMEM double buffer of windows
    meta_sems: object   # (3,) DMA semaphores of the metadata ring
    copy_sems: object   # (2, GROUP) DMA semaphores of the window gathers


def _meta(w: _Walk, mslot, g, k: int):
    """Word ``k`` of group ``g``'s :data:`META` metadata words (col_idx for
    k < GROUP, then the coarse adjacency words), read from the metadata
    row in ring slot ``mslot``."""
    return w.meta_ref[mslot, (g % GPR) * META + k]


def _meta_copy(w: _Walk, g, mslot):
    """DMA of the metadata row holding group ``g`` into ring slot
    ``mslot``: SMEM cannot hold a graph's FRDC arrays (1 MiB on v5e), so
    the walk streams each group's metadata just ahead of use."""
    return pltpu.make_async_copy(w.meta_hbm.at[pl.ds(g // GPR, 1)],
                                 w.meta_ref.at[pl.ds(mslot, 1)],
                                 w.meta_sems.at[mslot])


def _gather_copy(w: _Walk, mslot, g, t, slot, fb):
    """Step-② DMA descriptor: the aligned SLAB-row window that holds
    neighbor slab ``t`` of group ``g`` (4 rows at ``col_idx[g, t] * TILE``),
    feature block ``fb``, into double-buffer slot ``slot``.

    Built through ONE helper for both halves of the start/wait pair: a TPU
    DMA wait must be issued with the SAME descriptor (source slice,
    destination, semaphore) the copy was started with."""
    return pltpu.make_async_copy(
        w.x_hbm.at[fb, _meta(w, mslot, g, t) >> 1],
        w.xg_ref.at[slot, pl.ds(t * SLAB, SLAB)],
        w.copy_sems.at[slot, t])


def coarse_words(tiles: jax.Array, cols: jax.Array) -> jax.Array:
    """Step ③, vectorized over groups: (..., GROUP) 4x4 tiles + tile
    columns -> (..., 2*TILE) int32 words ``[lo_0..lo_3, hi_0..hi_3]``, one
    (lo, hi) pair per matrix row ``i`` of the tile-row over the group's
    GATHER gathered window rows. Tile ``t`` owns the 8-bit field ``t % 4``
    of word ``t // 4``; its row-``i`` nibble sits in the half of that field
    that holds its slab inside the window (``cols[t] & 1``). The fields are
    disjoint, so their int32 sum is their OR."""
    t32 = tiles.astype(jnp.int32)[..., None, :]                # (..., 1, 8)
    i = jnp.arange(TILE, dtype=jnp.int32)[:, None]             # (4, 1)
    t = jnp.arange(GROUP, dtype=jnp.int32)
    shift = (cols.astype(jnp.int32)[..., None, :] & 1) * TILE \
        + SLAB * (t % 4)
    field = ((t32 >> (i * TILE)) & 0xF) << shift               # (..., 4, 8)
    lo = jnp.sum(field[..., :4], axis=-1, dtype=jnp.int32)
    hi = jnp.sum(field[..., 4:], axis=-1, dtype=jnp.int32)
    return jnp.concatenate([lo, hi], axis=-1)


def group_mask(words, dtype) -> jax.Array:
    """(TILE, GATHER) {0,1} lane mask of a group from its coarse words
    (``words[i]`` = lo, ``words[TILE + i]`` = hi of row i): row ``i``, lane
    ``k`` = bit ``k % 32`` of lo (k < 32) or hi."""
    shape = (TILE, GATHER)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    w = jnp.full(shape, words[2 * TILE - 1], jnp.int32)
    for i in range(TILE):
        w = jnp.where((row == i) & (lane < WORD), words[i], w)
        if i < TILE - 1:
            w = jnp.where((row == i) & (lane >= WORD), words[TILE + i], w)
    return ((w >> (lane & (WORD - 1))) & 1).astype(dtype)


def bit_transpose(load, lanes: int) -> tuple:
    """Step ④: the (GATHER window rows, lanes) int32 words ``load(c)``
    returns SLAB rows ``c*SLAB..`` of -> (lo, hi), each (32 bits, lanes)
    int32: bit ``n`` of ``lo[f, w]`` is bit ``f`` of window row ``n``'s word
    ``w`` (rows 32..63 likewise in ``hi``)."""
    shape = (WORD, lanes)
    f_iota = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    half = WORD // SLAB

    def body(c, bt):
        lo_rows, hi_rows = load(c), load(c + half)
        lo, hi = bt
        for r in range(SLAB):
            n = c * SLAB + r
            lo = lo | (((lo_rows[r:r + 1] >> f_iota) & 1) << n)
            hi = hi | (((hi_rows[r:r + 1] >> f_iota) & 1) << n)
        return lo, hi

    zero = jnp.zeros(shape, jnp.int32)
    return jax.lax.fori_loop(0, half, body, (zero, zero))


def trinary(lo, hi, bt: tuple, trinary_s2: bool) -> jax.Array:
    """Step ⑤: trinary popc dot of one row's adjacency word pair against
    the bit-transposed window -> (32, lanes) int32 counts. Both modes give
    the same counts (``popc(a&~b) == popc(a) - popc(a&b)``)."""
    popc = jax.lax.population_count
    hit = popc(lo & bt[0]) + popc(hi & bt[1])
    if trinary_s2:
        return hit - popc(lo & ~bt[0]) - popc(hi & ~bt[1])
    wide = (1, bt[0].shape[1])
    deg = popc(jnp.full(wide, lo, jnp.int32)) \
        + popc(jnp.full(wide, hi, jnp.int32))
    return 2 * hit - deg


def _walk(w: _Walk, grp_ptr_ref, tr, fb, process):
    """Double-buffered walk over tile-row ``tr``'s group range.

    Groups come from the scalar-prefetched ``grp_ptr`` (``pad_frdc`` bucket
    padding lives past ``grp_ptr[-1]`` and is never visited). While group
    ``i`` is processed out of window slot ``i % 2``, group ``i+1``'s eight
    windows stream into the other slot, and group ``i+2``'s metadata row
    into the third slot of the metadata ring. ``process(mslot, g, slot)``
    consumes the gathered windows."""
    g_lo = grp_ptr_ref[tr]
    n_g = grp_ptr_ref[tr + 1] - g_lo

    @pl.when(n_g > 0)
    def _():
        first = _meta_copy(w, g_lo, 0)
        first.start()
        first.wait()

        @pl.when(n_g > 1)
        def _():
            _meta_copy(w, g_lo + 1, 1).start()
        for t in range(GROUP):
            _gather_copy(w, 0, g_lo, t, 0, fb).start()

    def body(i, _):
        g = g_lo + i
        mslot, slot = jax.lax.rem(i, 3), jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_g)
        def _():
            nxt = jax.lax.rem(i + 1, 3)
            _meta_copy(w, g + 1, nxt).wait()

            @pl.when(i + 2 < n_g)
            def _():
                _meta_copy(w, g + 2, jax.lax.rem(i + 2, 3)).start()
            for t in range(GROUP):
                _gather_copy(w, nxt, g + 1, t, jax.lax.rem(i + 1, 2),
                             fb).start()
        for t in range(GROUP):
            _gather_copy(w, mslot, g, t, slot, fb).wait()
        process(mslot, g, slot)
        return 0

    jax.lax.fori_loop(0, n_g, body, 0)


def _words(w: _Walk, mslot, g) -> list:
    """Group ``g``'s coarse adjacency words off its SMEM metadata row."""
    return [_meta(w, mslot, g, GROUP + k) for k in range(2 * TILE)]


def _fp_kernel(grp_ptr_ref, meta_hbm, x_hbm, out_ref, xg_ref, *ring,
               tb_rows: int):
    w = _Walk(meta_hbm, x_hbm, ring[0], xg_ref, ring[2], ring[1])
    rb, fb = pl.program_id(0), pl.program_id(1)
    out_ref[...] = jnp.zeros_like(out_ref)           # (tb_rows, TILE, lanes)

    def row(tb, _):
        def process(mslot, g, slot):
            out_ref[tb] += jax.lax.dot(
                group_mask(_words(w, mslot, g), xg_ref.dtype), xg_ref[slot],
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=out_ref.dtype)

        _walk(w, grp_ptr_ref, rb * tb_rows + tb, fb, process)
        return 0

    jax.lax.fori_loop(0, tb_rows, row, 0)


def _bits_kernel(grp_ptr_ref, meta_hbm, x_hbm, out_ref, *scratch,
                 tb_rows: int, trinary_s2: bool, binarize: bool,
                 n_feat: int, fbw: int):
    if binarize:
        acc_ref, xg_ref, *ring = scratch
    else:
        (xg_ref, *ring), acc_ref = scratch, out_ref
    w = _Walk(meta_hbm, x_hbm, ring[0], xg_ref, ring[2], ring[1])
    rb, fb = pl.program_id(0), pl.program_id(1)
    lanes = xg_ref.shape[-1]
    acc_ref[...] = jnp.zeros_like(acc_ref)          # (rows, WORD, lanes)

    def row(tb, _):
        def process(mslot, g, slot):
            words = _words(w, mslot, g)
            bt = bit_transpose(
                lambda c: xg_ref[slot, pl.ds(pl.multiple_of(c * SLAB, SLAB),
                                             SLAB)], lanes)
            for i in range(TILE):
                acc_ref[tb * TILE + i] += trinary(words[i], words[TILE + i],
                                                  bt, trinary_s2)

        _walk(w, grp_ptr_ref, rb * tb_rows + tb, fb, process)
        return 0

    jax.lax.fori_loop(0, tb_rows, row, 0)

    if binarize:
        # Step ⑥: feature bit f of word w <- sign of count [f, w]; the bits
        # are disjoint, so the int32 sum over f is their OR
        f_iota = jax.lax.broadcasted_iota(jnp.int32, (WORD, lanes), 0)
        for r in range(tb_rows * TILE):
            signs = (acc_ref[r] >= 0).astype(jnp.int32) << f_iota
            out_ref[r:r + 1, :] = jnp.sum(signs, axis=0, keepdims=True)
        if n_feat % WORD:
            tail = (1 << (n_feat % WORD)) - 1
            widx = fb * fbw + jax.lax.broadcasted_iota(jnp.int32,
                                                       (1, lanes), 1)
            out_ref[...] = out_ref[...] & jnp.where(
                widx == n_feat // WORD, tail, -1)


def _grid_dims(adj: FRDCMatrix, plan: BlockPlan, width: int):
    """Grid geometry + the grp_ptr cover for the padded row blocks.

    Returns (tb_rows, n_rb, fw, n_fb, grp_ptr) where ``grp_ptr`` is extended
    with repeats of its last value so every padded tile-row has an EMPTY
    group range (the pad groups past ``grp_ptr[-1]`` stay unvisited)."""
    tb_rows = plan.rows // TILE
    n_rb = -(-adj.n_tile_rows // tb_rows)
    fw = width if plan.feats is None else min(plan.feats, width)
    n_fb = -(-width // fw)
    gp = adj.grp_ptr
    extra = n_rb * tb_rows - adj.n_tile_rows
    if extra:
        gp = jnp.concatenate(
            [gp, jnp.broadcast_to(gp[-1], (extra,)).astype(gp.dtype)])
    return tb_rows, n_rb, fw, n_fb, gp


def _lane_pad(width: int) -> int:
    """Feature block width rounded up to whole 128-lane vregs: Mosaic only
    slices HBM operands at full lane tiles."""
    return -(-width // LANE) * LANE


def _windows(x: jax.Array, n_fb: int, fw: int) -> jax.Array:
    """(N, F) -> (n_fb, N8/SLAB, SLAB, lane-padded fw): zero-padded to
    whole windows and feature blocks, so every gather DMA moves one whole
    trailing (SLAB, lanes) block — the shape Mosaic accepts for a dynamic
    row offset."""
    n, f = x.shape
    x = jnp.pad(x, ((0, (-n) % SLAB), (0, n_fb * fw - f)))
    x = x.reshape(-1, SLAB, n_fb, fw)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, _lane_pad(fw) - fw)))
    return x.transpose(2, 0, 1, 3)


def _meta_rows(adj: FRDCMatrix) -> jax.Array:
    """(G, 8) col_idx + (G, 8) coarse words -> (ceil(G/GPR), 128) int32
    rows, GPR groups of META words each: the HBM layout the walk streams."""
    g = adj.n_groups
    meta = jnp.concatenate([adj.col_idx.astype(jnp.int32),
                            coarse_words(adj.tiles, adj.col_idx)], axis=1)
    meta = jnp.pad(meta, ((0, (-g) % GPR), (0, 0)))
    return meta.reshape(-1, GPR * META)


def _launch(kernel, adj: FRDCMatrix, gp, xw, grid, block, out_dtype,
            scratch, interpret: Optional[bool]):
    """One grid launch: output ``grid + block``, one block per step."""
    zeros = (0,) * len(block)
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[hbm, hbm],
            out_specs=pl.BlockSpec(
                (pl.Squeezed(), pl.Squeezed()) + block,
                lambda rb, fb, gp_: (rb, fb) + zeros),
            scratch_shapes=scratch + [
                pltpu.SMEM((3, GPR * META), jnp.int32),
                pltpu.SemaphoreType.DMA((2, GROUP)),
                pltpu.SemaphoreType.DMA((3,))],
        ),
        out_shape=jax.ShapeDtypeStruct(grid + block, out_dtype),
        interpret=resolve_interpret(interpret),
    )(gp, _meta_rows(adj), xw)


def bspmm_bits(adj: FRDCMatrix, x_packed: jax.Array, n_feat: int | None = None,
               binarize: bool = True, trinary_mode: str = "s3_two_popc",
               interpret: Optional[bool] = None,
               block_shape=None) -> jax.Array:
    """FRDC trinary aggregation of packed ±1 activations (Algorithm 1).

    ``x_packed``: (N, Wf) uint32. Returns (R4, Wf) uint32 bits when
    ``binarize`` else (R4, Wf*32) int32 counts, R4 = n_tile_rows*4 (crop to
    n_rows at the caller). Rows with no groups give 0 counts / all-ones
    bits == sign(0). ``block_shape``: the (rows, feats) grid tunable
    (word-aligned feature blocks; see :func:`block_probe`); None is
    ``(DEFAULT_ROWS, full width)``. ``interpret``: None = native on a TPU
    backend, Pallas interpret mode elsewhere.
    """
    wf = x_packed.shape[1]
    f = wf * WORD if n_feat is None else int(n_feat)
    # validate the block tunable against the ACTUAL feature width (a caller
    # may serve n_feat narrower than the padded word width wf * WORD)
    plan = _block_plan(block_shape, f, packed_width=True)
    feats_w = None if (plan.feats is None or plan.feats % WORD) \
        else plan.feats // WORD
    tb_rows, n_rb, fbw, n_fb, gp = _grid_dims(
        adj, BlockPlan(plan.rows, feats_w), wf)
    xw = _windows(jax.lax.bitcast_convert_type(x_packed, jnp.int32),
                  n_fb, fbw)
    rows = tb_rows * TILE
    r4 = adj.n_tile_rows * TILE

    lanes = _lane_pad(fbw)
    scratch = [pltpu.VMEM((2, GATHER, lanes), jnp.int32)]
    if binarize:
        block = (rows, lanes)
        scratch = [pltpu.VMEM((rows, WORD, lanes), jnp.int32)] + scratch
    else:
        block = (rows, WORD, lanes)
    kernel = functools.partial(
        _bits_kernel, tb_rows=tb_rows,
        trinary_s2=(trinary_mode == "s2_and_andnot"),
        binarize=binarize, n_feat=f, fbw=fbw)
    out = _launch(kernel, adj, gp, xw, (n_rb, n_fb), block, jnp.int32,
                  scratch, interpret)[..., :fbw]
    if binarize:
        out = out.transpose(0, 2, 1, 3).reshape(n_rb * rows, n_fb * fbw)
        return jax.lax.bitcast_convert_type(out[:r4, :wf], jnp.uint32)
    # (rb, fb, row, bit, word) -> (row, word, bit): feature = word*32 + bit
    out = out.transpose(0, 2, 1, 4, 3).reshape(n_rb * rows,
                                               n_fb * fbw * WORD)
    return out[:r4, :wf * WORD]


def bspmm_fp(adj: FRDCMatrix, x: jax.Array, interpret: Optional[bool] = None,
             block_shape=None) -> jax.Array:
    """FRDC aggregation of fp activations via MXU mask-matmul (BSpMM.FB?).

    ``x``: (N, F) float. Returns (R4, F) float; caller applies row/col scales
    and crops to n_rows. Col scales must already be folded into ``x``.
    ``block_shape``: the (rows, feats) grid tunable — feats zero-padded to
    the block grid (exact); None is ``(DEFAULT_ROWS, full width)`` (see
    :func:`block_probe` for the legal space).
    """
    f = x.shape[1]
    plan = _block_plan(block_shape, f, packed_width=False)
    tb_rows, n_rb, fw, n_fb, gp = _grid_dims(adj, plan, f)
    kernel = functools.partial(_fp_kernel, tb_rows=tb_rows)
    lanes = _lane_pad(fw)
    out = _launch(kernel, adj, gp, _windows(x, n_fb, fw), (n_rb, n_fb),
                  (tb_rows, TILE, lanes), x.dtype,
                  [pltpu.VMEM((2, GATHER, lanes), x.dtype)],
                  interpret)[..., :fw]
    # (rb, fb, tile-row, row, feat) -> (rows, features)
    out = out.transpose(0, 2, 3, 1, 4).reshape(n_rb * tb_rows * TILE,
                                               n_fb * fw)
    return out[:adj.n_tile_rows * TILE, :f]
