"""The ``sage-reddit`` configuration's files: found by name, its work count
against a hand count, its reference (edge-sliced aggregation) against an
unsliced one and against the program's own binarized forward, and a whole
run of its cell at a small Reddit size on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import correctness, faults, run, spec
from chipbench.reference import _common
from chipbench.reference import sage as ref_sage
from chipbench.work import sage
from repro.graphs.datasets import make_dataset
from repro.models import gnn

CELL = "sage-reddit.fresh-full"
BENCH = spec.benchmark()
SMALL = {"config": {"dataset": {"name": "reddit", "seed": 0,
                                "scale": 0.004}},
         "cell": {"query_rate_per_s": 2000.0, "update_rate_per_s": 20.0}}
SEED = 2 ** 31 + 13


def test_cell_files_found_by_name():
    c = spec.cell(CELL)
    cfg = spec.config(c["config"])
    assert cfg["family"] == "sage" and cfg["hidden"] == 256
    assert (cfg["n_features"], cfg["n_classes"]) == (602, 41)
    assert spec.traffic(c["traffic"])["mode"] == "full"
    assert spec.family_module("reference", "sage") is ref_sage
    assert spec.family_module("work", "sage") is sage
    assert [m["name"] for m in spec.end_to_end(BENCH, CELL)] == [
        "stale_p90_ms", "setup_s"]
    names = [m["name"] for m in spec.per_layer(BENCH, CELL)]
    assert names == [f"{q}.sage-reddit" for q in (
        "refresh_ms", "forward_device_ms", "forward_roofline",
        "forward_mfu", "device_idle_share", "lookup_tick_ms")]
    for name in names:
        spec.reader(name)


# 10 nodes, 24 directed edges, 16 features, hidden 8, 3 classes
R, E, F, H, C = 10, 24, 16, 8, 3


def test_sage_counts():
    ops, byt = sage.forward(R, E, F, H, C)
    hand_ops = (2 * 2 * 10 * 16 * 8 + 2 * 24 * 8 + 10 * 8
                + 2 * 2 * 10 * 8 * 3 + 2 * 24 * 3 + 10 * 3)
    assert ops == hand_ops
    w = lambda k, n: k * n / 8 + n * 4          # noqa: E731
    adj = 24 * 4 + 11 * 4 + 10 * 4
    hand_bytes = (10 * 16 * 4 + 2 * w(16, 8) + adj + 10 * 8 * 4
                  + 10 * 8 * 4 + 2 * w(8, 3) + adj + 10 * 3 * 4)
    assert byt == hand_bytes


def test_reddit_counts():
    # the two 602 x 256 transforms are most of the ops, yet bytes bound
    # the forward
    ops, byt = sage.forward(232_965, 74_933_898, 602, 256, 41)
    assert 2 * 2 * 232_965 * 602 * 256 < ops < 2.1e11
    assert 1.5e9 < byt < 2.5e9
    assert byt / 819e9 > ops / 197e12


@pytest.fixture(scope="module")
def graph():
    return make_dataset("reddit", seed=0, scale=0.004)


@pytest.mark.parametrize("chunk", [1, 1000, 4096, 1 << 20])
def test_sliced_aggregation_matches_unsliced(graph, chunk):
    """Summing over slices of ``chunk`` edges (the last padded onto a row
    past the graph) gives the mean of one segment-sum over every edge, to
    float32 reordering (degrees here are below 300: 1e-5 relative)."""
    d = graph
    n = d.n_nodes
    y = np.random.default_rng(0).standard_normal((n, 24)).astype(np.float32)
    e = (jnp.asarray(d.edges[0]), jnp.asarray(d.edges[1]))
    deg = np.bincount(d.edges[0], minlength=n)
    want = _common.aggregate(e, jnp.asarray(y), n,
                             row_scale=jnp.asarray(
                                 1.0 / np.maximum(deg, 1), jnp.float32))
    got = ref_sage.mean_aggregate(e, jnp.asarray(y), n, chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def _ref(params, d, dtype="float32"):
    return correctness.reference_logits(
        ref_sage.forward, [np.asarray(p) for p in params], {0: d.x},
        d.edges, dtype=dtype)[0]


@pytest.mark.parametrize("seed", [0, 1])
def test_sage_reference_matches_program(graph, seed):
    d = graph
    p = gnn.init_sage(jax.random.PRNGKey(seed), d.x.shape[1], 256, 41)
    prog = np.asarray(gnn.sage_forward_bitgnn(
        gnn.quantize_sage(p), d.x, d.adjacency("mean")))
    err = correctness.row_errors(prog, _ref(p, d))
    assert err.max() < 1e-4, err.max()


def test_bfloat16_control_departs(graph):
    d = graph
    p = gnn.init_sage(jax.random.PRNGKey(0), d.x.shape[1], 256, 41)
    hi, lo = _ref(p, d), _ref(p, d, "bfloat16")
    assert np.median(correctness.row_errors(lo, hi)) > 1e-3


def test_sage_fragile_rule(graph):
    d = graph
    p = [np.asarray(w) for w in
         gnn.init_sage(jax.random.PRNGKey(0), d.x.shape[1], 256, 41)]

    class Eps:
        def __init__(self, eps):
            self.eps = eps

        def fragile(self, params, x, edges, n, _eps):
            return ref_sage.fragile(params, x, edges, n, self.eps)

    masks = {eps: correctness.reference_fragile(
        Eps(eps), p, {0: d.x}, d.edges)[0] for eps in (0.0, 1e-5, 1e9)}
    assert not masks[0.0].any()
    assert masks[1e9].all()
    assert masks[1e-5].sum() <= masks[1e9].sum()


def _run(**kw):
    return run.run_cell(CELL, SEED, 1.0, False, require_tpu=False,
                        overrides=SMALL, **kw)


def test_sound_run_is_correct_and_control_is_not():
    res = _run(control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["_info"]["plan"]["full_cache"] is True
    assert res["_info"]["control_correct"] is False, res["_info"]["control"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_broken_timed_path_is_not_correct(fault):
    res = _run(patch=faults.FAULTS[fault])
    assert not res["correct"], (fault, res["checks"])


def test_traced_run_reads_its_layers():
    """Off the chip the trace holds no device plane: the device readers
    return nothing and are left out, the host ones (``refresh_ms``,
    ``lookup_tick_ms``) are read."""
    res = run.run_cell(CELL, SEED, 1.0, True, require_tpu=False,
                       overrides=SMALL)
    assert res["correct"]
    assert res["device"]["busy_s"] is None
    host = {"refresh_ms.sage-reddit", "lookup_tick_ms.sage-reddit"}
    assert set(res["metrics"]) == host
    for name in host:
        assert res["metrics"][name]["value"] > 0
