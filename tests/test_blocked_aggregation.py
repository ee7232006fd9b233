"""The fp aggregation over blocks of groups (``core/bspmm._spmm_fp``): the
same sums as one block whatever the block size, one block (and the very
program of a single gather) wherever the groups fit, and GraphSAGE served
on the full-cache path through it against the plain float32 reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bspmm, frdc
from repro.core.frdc import TILE, coarsen_groups, group_neighbor_ids
from repro.graphs.datasets import make_dataset
from repro.models import gnn
from repro.serve import GNNServeEngine, GraphStore

jax.config.update("jax_platform_name", "cpu")

F = 16                      # one 128-lane row per gathered neighbour
GROUP_BYTES = frdc.GROUP_COLS * bspmm.LANES * 4


def _one_block_spmm_fp(adj, x):
    """The fp aggregation as a single gather and segment-sum: the whole of
    ``_spmm_fp`` before it ran in blocks, kept verbatim as the oracle of the
    one-block program."""
    if adj.col_scale is not None:
        x = x * adj.col_scale[:, None].astype(x.dtype)
    xp = bspmm._pad_rows(x, TILE)
    nbr = group_neighbor_ids(adj.col_idx)          # (G, 32)
    xg = xp[nbr]                                   # (G, 32, F)
    mask = bspmm._mask_from_words(coarsen_groups(adj.tiles)).astype(x.dtype)
    contrib = jnp.einsum("gkn,gnf->gkf", mask, xg,
                         precision=jax.lax.Precision.HIGHEST)
    out = bspmm._segment_rows(contrib, adj)
    if adj.row_scale is not None:
        out = out * adj.row_scale[:, None].astype(out.dtype)
    return out


def _graph(n, density, seed, row_scale, col_scale):
    rng = np.random.default_rng(seed)
    a = rng.random((n, n)) < density
    r, c = np.nonzero(a)
    rs = rng.uniform(0.1, 1.0, n) if row_scale else None
    cs = rng.uniform(0.1, 1.0, n) if col_scale else None
    x = rng.standard_normal((n, F)).astype(np.float32)
    return frdc.from_coo(r, c, n, n, row_scale=rs, col_scale=cs), x


@pytest.mark.parametrize("row_scale,col_scale",
                         [(False, False), (True, False), (True, True)],
                         ids=["sum", "mean", "both"])
@pytest.mark.parametrize("block", [1, 3, 7])
def test_blocked_sums_match_one_block(monkeypatch, row_scale, col_scale,
                                      block):
    """Blocks of ``block`` groups (the last padded with empty groups) give
    the one-block sums. Tolerance: each output is a sum of at most 200
    products of O(1) values added in another order, so float32 rounding
    moves it by at most about 200 * 2**-24 * |terms| < 3e-5 of its terms'
    magnitude; 1e-5 relative with 1e-4 absolute leaves room on both."""
    adj, x = _graph(200, 0.3, block, row_scale, col_scale)
    want = np.asarray(bspmm._spmm_fp(adj, jnp.asarray(x)))
    monkeypatch.setattr(bspmm, "FP_BLOCK_BYTES", block * GROUP_BYTES)
    assert bspmm.fp_block_groups(F, jnp.float32) == block
    assert bspmm.fp_blocks(adj, F) == -(-adj.n_groups // block) > 1
    fn = jax.jit(lambda v: bspmm._spmm_fp(adj, v))
    assert "while" in fn.lower(jnp.asarray(x)).as_text()
    got = np.asarray(fn(jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    dense = np.asarray(frdc.to_dense(adj))
    np.testing.assert_allclose(got, dense @ x, rtol=1e-5, atol=1e-4)


def test_block_size_counts_lanes():
    """A block's gather fills 4 GiB as the chip lays it out: widths up to
    128 take one 128-lane row, so width 41 blocks as 128 does."""
    assert bspmm.fp_block_groups(256, jnp.float32) == 131_072
    assert bspmm.fp_block_groups(41, jnp.float32) == 262_144
    assert bspmm.fp_block_groups(128, jnp.float32) == 262_144
    assert bspmm.fp_block_groups(129, jnp.float32) == 131_072
    # Reddit's 9,078,090 groups at the configuration's widths
    assert -(-9_078_090 // bspmm.fp_block_groups(256, jnp.float32)) == 70
    assert -(-9_078_090 // bspmm.fp_block_groups(41, jnp.float32)) == 35


# Flickr's full forward (112,973 groups) and its subgraph bucket
# (131,072 groups, 89,252 nodes) at hidden 256; GCN's hidden 64
@pytest.mark.parametrize("n,groups,width,scaled", [
    (89_250, 112_973, 256, False), (89_252, 131_072, 256, False),
    (89_250, 112_973, 64, True)], ids=["flickr-full", "flickr-bucket",
                                       "gcn-64"])
def test_one_block_program_is_unchanged(n, groups, width, scaled):
    """Where the groups fit one block the aggregation lowers to the very
    program of the single gather it was before blocks: the same StableHLO,
    no loop."""
    s = jax.ShapeDtypeStruct
    args = (s((groups, frdc.GROUP), jnp.uint16),
            s((groups, frdc.GROUP), jnp.int32), s((groups,), jnp.int32),
            s((groups,), jnp.int32), s((-(-n // TILE) + 1,), jnp.int32),
            s((n, width), jnp.float32), s((n,), jnp.float32))

    def lowered(fn):
        def aggregate(tiles, col_idx, group_row, group_first, grp_ptr, x,
                      scale):
            adj = frdc.FRDCMatrix(tiles, col_idx, group_row, group_first,
                                  grp_ptr, n, n, 0,
                                  scale if scaled else None,
                                  scale if scaled else None)
            return fn(adj, x)
        return jax.jit(aggregate).lower(*args).as_text()

    assert bspmm.fp_block_groups(width, jnp.float32) >= groups
    got = lowered(bspmm._spmm_fp)
    assert got == lowered(_one_block_spmm_fp)
    assert "while" not in got


@pytest.fixture(scope="module")
def reddit():
    return make_dataset("reddit", seed=0, scale=0.004)


@pytest.mark.parametrize("block", [None, 5], ids=["one-block", "blocked"])
def test_sage_full_cache_serves_the_reference(monkeypatch, reddit, block):
    """Binary GraphSAGE served on the full-cache path (``GraphStore`` and
    ``GNNServeEngine(mode="full")``, the path of ``sage-reddit.fresh-full``)
    against the plain float32 reference of the benchmark, on seeded random
    weights, in one block and in blocks of 5 groups. Tolerance: the
    benchmark's own limits (``chipbench/cells/sage-reddit.fresh-full.json``)
    sit at 1e-4 (median) and 0.05 (largest robust row); the program's
    float32 reductions read about 1e-7 here."""
    from chipbench import correctness
    from chipbench.reference import sage as ref_sage

    if block is not None:
        monkeypatch.setattr(bspmm, "FP_BLOCK_BYTES",
                            block * frdc.GROUP_COLS * 256 * 4)
    d = reddit
    params = gnn.init_sage(jax.random.PRNGKey(3), d.x.shape[1], 256,
                           d.n_classes)
    store = GraphStore(max_batch=32)
    store.register_graph("g", d)
    store.register_model("m", "sage", params)
    engine = GNNServeEngine(store, max_batch=32, mode="full")
    nodes = np.random.default_rng(4).integers(0, d.n_nodes, size=96)
    queries = engine.submit_many("g", "m", nodes)
    engine.run_until_drained()
    session = store.session("g", "m")
    groups = session._adj_full["mean"].n_groups
    if block is None:
        assert session.aggregation_blocks == (1, 1)
    else:
        # widths 256 and 41 each fill whole 128-lane rows: 2 and 1 rows
        assert session.aggregation_blocks == (-(-groups // block),
                                              -(-groups // (2 * block)))
    served = np.stack([q.logits for q in queries])
    hp = [np.asarray(p) for p in params]
    ref = correctness.reference_logits(ref_sage.forward, hp, {0: d.x},
                                       d.edges)[0]
    frag = correctness.reference_fragile(ref_sage, hp, {0: d.x},
                                         d.edges)[0]
    nums = correctness.numbers(served, ref[nodes], 0, 0, frag[nodes])
    assert nums["median_err"] < 1e-5 and nums["max_err"] < 1e-4, nums
    assert nums["class_disagree"] == 0.0


@pytest.mark.parametrize("family", ["gcn", "sage", "saint"])
def test_aggregation_blocks_name_the_loops_of_the_full_forward(
        monkeypatch, reddit, family):
    """``CompiledGraphSession.aggregation_blocks`` gives one entry per fp
    aggregation of the full forward, and each entry above one is a loop of
    that forward's program."""
    monkeypatch.setattr(bspmm, "FP_BLOCK_BYTES",
                        5 * frdc.GROUP_COLS * 256 * 4)
    d = reddit
    init = getattr(gnn, f"init_{family}")
    store = GraphStore(max_batch=32)
    store.register_graph("g", d)
    store.register_model("m", family, init(jax.random.PRNGKey(0),
                                           d.x.shape[1], 256, d.n_classes))
    session = store.session("g", "m")
    blocks = session.aggregation_blocks
    fp = {"gcn": 2 if session.plan.scheme == "full" else 1}.get(family, 2)
    assert len(blocks) == fp and min(blocks) > 1
    text = session._jit_full.lower(jnp.asarray(d.x)).as_text()
    assert text.count("stablehlo.while") == len(blocks)
