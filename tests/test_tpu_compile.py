"""Compile the serving path's kernels and forward for a TPU v5e that is
described, not attached (``jax.experimental.topologies``): what Mosaic or
XLA refuses here would be refused on the chip.

The topology is described only inside the module-scoped fixture: only one
process at a time may load the TPU library, so it must not happen while
test modules are imported.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.binarize import BinTensor
from repro.core.frdc import FRDCMatrix, GROUP, TILE
from repro.kernels import bspmm_kernel
from repro.kernels import ops as kernel_ops
from repro.models import gnn
from repro.serve import session_core

# serving widths: GCN hidden 64; Flickr 500, Reddit 602, CiteSeer 3703 features
WIDTHS = (64, 500, 602, 3703)
N_NODES, N_GROUPS = 8192, 16384       # a 2-hop subgraph bucket's scale


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # the TPU compiler's threads inherit this thread's affinity: one core
    # keeps these compiles from starving timing-sensitive tests that run
    # beside them (tests/test_trace.py's overhead bound)
    cpus = os.sched_getaffinity(0)
    os.sched_setaffinity(0, {min(cpus)})
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        os.sched_setaffinity(0, cpus)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _frdc_shapes(sharding, n: int, g: int) -> tuple:
    return (_sds(sharding, (g, GROUP), jnp.uint16),
            _sds(sharding, (g, GROUP), jnp.int32),
            _sds(sharding, (g,), jnp.int32),
            _sds(sharding, (g,), jnp.int32),
            _sds(sharding, (-(-n // TILE) + 1,), jnp.int32))


@pytest.mark.parametrize("f", WIDTHS)
@pytest.mark.parametrize("kind", ["fp", "bits"])
def test_bspmm_compiles_for_v5e(one_chip, kind, f):
    """Both BSpMM kernels lower through Mosaic at the serving block shape
    (the default ``(8, full width)``), natively (``interpret=False``)."""
    n = N_NODES

    def fn(tiles, col_idx, group_row, group_first, grp_ptr, x):
        adj = FRDCMatrix(tiles, col_idx, group_row, group_first, grp_ptr,
                         n, n, 0)
        if kind == "fp":
            return bspmm_kernel.bspmm_fp(adj, x, interpret=False)
        return bspmm_kernel.bspmm_bits(adj, x, f, binarize=False,
                                       interpret=False)

    x = (_sds(one_chip, (n, f), jnp.float32) if kind == "fp"
         else _sds(one_chip, (n, -(-f // 32)), jnp.uint32))
    compiled = jax.jit(fn).lower(*_frdc_shapes(one_chip, n, N_GROUPS),
                                 x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp", "pallas"])
def test_gcn_serving_forward_compiles_for_v5e(one_chip, use_pallas,
                                              monkeypatch):
    """The full-graph GCN serving forward (BN calibration pass) of the
    stat-matched Flickr graph fits one v5e, on the jnp path and on the
    Pallas path (the backend check steered to a TPU here)."""
    from repro.graphs.datasets import make_dataset
    if use_pallas:
        monkeypatch.setattr(kernel_ops, "_on_tpu", lambda: True)
        monkeypatch.setattr(bspmm_kernel, "resolve_interpret",
                            lambda interpret=None: False)
    d = make_dataset("flickr", seed=0, scale=1.0)
    adjs = {"adj": d.adjacency("gcn"), "bin": d.adjacency("binary")}
    q = session_core.quantize_family(
        "gcn", gnn.init_gcn(jax.random.PRNGKey(0), d.x.shape[1], 64,
                            d.n_classes))
    plan = session_core.default_plan("gcn")
    arrs = {k: session_core.frdc_arrays(m) for k, m in adjs.items()}
    bits = [t.n for t in q]

    def fwd(x, ar, qp):
        mats = {k: session_core.frdc_rebuild(ar[k], adjs[k].n_rows,
                                             adjs[k].n_cols, adjs[k].nnz)
                for k in ar}
        qq = type(q)(*(BinTensor(packed=p, scale=s, n=n)
                       for (p, s), n in zip(qp, bits)))
        return session_core.family_forward(plan, qq, x, mats,
                                           use_pallas=use_pallas,
                                           return_bn_stats=True)

    shape = lambda a: _sds(one_chip, np.shape(a), a.dtype)  # noqa: E731
    compiled = jax.jit(fwd).lower(
        _sds(one_chip, d.x.shape, jnp.float32), jax.tree.map(shape, arrs),
        [(shape(t.packed), shape(t.scale)) for t in q]).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_pallas
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def _forward_compile(one_chip, family, n, f_in, hidden, classes, groups,
                     kind, row_scale):
    """Compile ``family``'s full-graph serving forward (BN calibration
    pass, jnp path) from shapes alone: ``n`` nodes of ``f_in`` features
    over one FRDC adjacency ``kind`` of ``groups`` groups."""
    init = getattr(gnn, f"init_{family}")
    q = session_core.quantize_family(
        family, init(jax.random.PRNGKey(0), f_in, hidden, classes))
    plan = session_core.default_plan(family)
    bits = [t.n for t in q]
    arrs = dict(zip(("tiles", "col_idx", "group_row", "group_first",
                     "grp_ptr"), _frdc_shapes(one_chip, n, groups)))
    if row_scale:
        arrs["row_scale"] = _sds(one_chip, (n,), jnp.float32)

    def fwd(x, ar, qp):
        mats = {kind: session_core.frdc_rebuild(ar, n, n)}
        qq = type(q)(*(BinTensor(packed=p, scale=s, n=b)
                       for (p, s), b in zip(qp, bits)))
        return session_core.family_forward(plan, qq, x, mats,
                                           return_bn_stats=True)

    shape = lambda a: _sds(one_chip, np.shape(a), a.dtype)  # noqa: E731
    return jax.jit(fwd).lower(
        _sds(one_chip, (n, f_in), jnp.float32), arrs,
        [(shape(t.packed), shape(t.scale)) for t in q]).compile()


def test_sage_reddit_forward_fits_v5e(one_chip):
    """The ``sage-reddit`` full forward on the jnp path (232,965 nodes, 602
    features, hidden 256, 41 classes; the 9,078,090 groups of
    ``make_dataset("reddit", seed=0)``'s mean adjacency) fits one v5e with
    room: its aggregations run 70 blocks at width 256 and 35 at width 41,
    where one gather of every group at width 256 would ask for 297 GB."""
    compiled = _forward_compile(one_chip, "sage", 232_965, 602, 256, 41,
                                9_078_090, "mean", True)
    # each loop carries its adjacency's tiles cut into blocks of groups
    text = compiled.as_text()
    assert "u16[70,131072,8]" in text and "u16[35,262144,8]" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12e9


@pytest.mark.parametrize("n,groups", [(89_250, 112_973), (89_252, 131_072)],
                         ids=["full", "bucket"])
def test_saint_flickr_forward_is_one_block(one_chip, n, groups):
    """The ``saint-flickr`` forwards, over the whole graph (112,973 groups)
    and at the subgraph bucket (89,252 nodes, 131,072 groups), aggregate
    in one block each, with no loop in the compiled program."""
    compiled = _forward_compile(one_chip, "saint", n, 500, 256, 7, groups,
                                "sum", False)
    assert "while" not in compiled.as_text()
