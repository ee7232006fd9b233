"""2D BSpMM block grid + fused per-layer kernel + multi-bucket launch tests.

The three guarantees of the kernel-plan stack, checked end to end:

  * the (rows, feats) block grid is BITWISE identical to the default
    block shape for every legal block shape, and matches the dense oracles
    (property sweep over rows/feats/n_feat/tile count; hypothesis widens
    the sweep when installed);
  * the fused per-layer path is one Pallas launch per layer and bitwise
    identical to the unfused serve path — verified through the replayed
    ``batch_log`` oracle, which compares jitted vs jitted (the fused
    guarantee; eager-vs-jit differs by XLA fusion rounding);
  * the multi-bucket co-launch dispatches several padded pow2 buckets as
    one jitted program per serve core — fewer dispatches per tick, same
    bits, visible in the span traces as shared coalesced launch windows.

Plus the persistence seams they ride on: the tuner cache file format and
``GraphStore`` seeding, the ``SessionPlan.fused`` artifact roundtrip and
its refusal on a TPU backend, and the ``repro.env`` compile-cache helper.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bitops, frdc
from repro.kernels import bspmm_kernel, fused_layer, ref
from repro.kernels import ops as kernel_ops
from repro.graphs.datasets import make_dataset
from repro.models import gnn
from repro.serve import GNNServeEngine, GraphStore
from repro.serve.trace import SpanTracer

try:
    from hypothesis import given, settings
    from hypothesis import strategies as hst
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

jax.config.update("jax_platform_name", "cpu")

BATCH = 8
HIDDEN = 16


# ---------------------------------------------------------------- 2D grid ---

def _case(seed: int, n: int, f: int, rows: int, feats):
    """One property-sweep case: a block shape must match the default block
    BITWISE (fp and counts), the fp oracle to fp tolerance and the counts
    oracle exactly."""
    rng = np.random.default_rng(seed)
    adj = frdc.from_dense((rng.random((n, n)) < 0.2).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    got = bspmm_kernel.bspmm_fp(adj, x, block_shape=(rows, feats))
    base = bspmm_kernel.bspmm_fp(adj, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    np.testing.assert_allclose(np.asarray(got[: adj.n_rows]),
                               np.asarray(ref.bspmm_fp_ref(adj, x))[
                                   : adj.n_rows],
                               rtol=1e-5, atol=1e-5)
    xp = bitops.pack_bits(rng.choice([-1.0, 1.0], size=(n, f)) > 0)
    # packed feature blocks must stay word-aligned (or the real width)
    bits_blk = (rows, None) if (feats is not None and feats % 32) \
        else (rows, feats)
    for binarize in (False, True):
        got_b = bspmm_kernel.bspmm_bits(adj, xp, f, binarize=binarize,
                                        block_shape=bits_blk)
        base_b = bspmm_kernel.bspmm_bits(adj, xp, f, binarize=binarize)
        np.testing.assert_array_equal(np.asarray(got_b), np.asarray(base_b))
        want_b = np.asarray(ref.bspmm_bits_ref(adj, xp, f,
                                               binarize=binarize))
        # the counts kernel carries the word-padded width; the oracle the
        # real one
        np.testing.assert_array_equal(
            np.asarray(got_b)[: adj.n_rows, : want_b.shape[1]],
            want_b[: adj.n_rows])


# (seed, n, f, rows, feats): tile counts 1..17, narrow/wide/ragged feature
# widths, single- and multi-row blocks, full-width and blocked features
GRID_SWEEP = [
    (0, 4, 32, 4, None),          # one tile row, minimal
    (1, 16, 32, 8, 32),           # rows > tile, exact feature block
    (2, 30, 64, 8, 32),           # ragged node count (pads to tile)
    (3, 33, 96, 12, 64),          # feats not dividing f (fp zero-pads)
    (4, 40, 24, 4, 24),           # f narrower than one word, real-width blk
    (5, 64, 128, 16, 64),         # many tile rows, wide block
    (6, 17, 40, 8, None),         # full-width multi-row
    (7, 68, 32, 32, 32),          # block rows > some row groups
]


@pytest.mark.parametrize("seed,n,f,rows,feats", GRID_SWEEP)
def test_grid_matches_single_block_and_reference(seed, n, f, rows, feats):
    _case(seed, n, f, rows, feats)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(seed=hst.integers(0, 2**16),
           n=hst.integers(2, 70),
           f=hst.sampled_from([24, 32, 40, 64, 96, 128]),
           rows=hst.sampled_from([4, 8, 12, 16, 32]),
           feats=hst.sampled_from([None, 24, 32, 64, 128]))
    def test_grid_property_sweep(seed, n, f, rows, feats):
        _case(seed, n, f, rows, feats)


# ------------------------------------------------------------- fused path ---

@pytest.fixture(scope="module")
def data():
    return make_dataset("cora", seed=0, scale=0.1)


def _store(data, **kw):
    st = GraphStore(max_batch=BATCH, **kw)
    st.register_graph("g", data)
    key = jax.random.PRNGKey(0)
    f, c = data.x.shape[1], data.n_classes
    st.register_model("gcn", "gcn", gnn.init_gcn(key, f, HIDDEN, c))
    st.register_model("sage", "sage", gnn.init_sage(key, f, HIDDEN, c))
    st.register_model("saint", "saint", gnn.init_saint(key, f, HIDDEN, c))
    return st


@pytest.fixture(autouse=True)
def _kernels_on():
    kernel_ops.force_kernels(True)
    yield
    kernel_ops.force_kernels(False)


N_LAYERS = {"gcn": 2, "sage": 2, "saint": 3}


@pytest.mark.parametrize("model", ["gcn", "sage", "saint"])
def test_fused_serve_bitwise_and_one_launch_per_layer(data, model):
    """The fused session serves bitwise identically to the unfused one AND
    traces exactly ONE fused kernel launch per layer (the launches-per-layer
    regression: the unfused path costs several dispatches per layer)."""
    seeds = np.random.default_rng(0).integers(0, data.n_nodes, size=BATCH)
    want = _store(data, use_pallas=True).session("g", model) \
        .serve_subgraph(seeds)
    sess = _store(data, use_pallas=True, fused=True).session("g", model)
    assert sess.plan.fused and "|fused" in sess.plan.name()
    fused_layer.reset_counters()
    got = sess.serve_subgraph(seeds)
    assert fused_layer.KERNEL_CALLS["fused"] == N_LAYERS[model]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # steady state: a second serve of the same bucket traces nothing new
    sess.serve_subgraph(seeds)
    assert fused_layer.KERNEL_CALLS["fused"] == N_LAYERS[model]


@pytest.mark.parametrize("model", ["gcn", "sage"])
def test_fused_engine_replay_oracle(data, model):
    """Engine-served fused answers replay bitwise against the unfused
    session on the engine's ACTUAL batch compositions (the batch_log
    oracle — jitted fused vs jitted unfused)."""
    st = _store(data, use_pallas=True, fused=True)
    engine = GNNServeEngine(st, max_batch=BATCH, mode="subgraph")
    nodes = np.random.default_rng(1).integers(0, data.n_nodes,
                                              size=3 * BATCH)
    queries = engine.submit_many("g", model, nodes)
    engine.run_until_drained()
    assert all(q.done for q in queries)
    unfused = _store(data, use_pallas=True).session("g", model)
    assert engine.batch_log
    for batch in engine.batch_log:
        want = unfused.serve_subgraph(np.asarray([q.node for q in batch]))
        np.testing.assert_array_equal(
            np.stack([q.logits for q in batch]), want)


# ------------------------------------------------------ multi-bucket tick ---

def test_multi_bucket_tick_one_dispatch(data):
    """A multi-bucket pipeline tick co-launches every bucket it extracted
    as ONE device dispatch per serve core: the dispatch counter moves by 1
    where the serial engine moves by K, the co-launched batches' launch
    spans share one wall window tagged with the coalesced bucket count,
    and the answers replay bitwise against a serial session."""
    nodes = np.random.default_rng(2).integers(0, data.n_nodes,
                                              size=6 * BATCH)
    serial = GNNServeEngine(_store(data), max_batch=BATCH, mode="subgraph",
                            pipeline_depth=2)
    qs = serial.submit_many("g", "gcn", nodes)
    serial.run_until_drained()

    st = _store(data)
    engine = GNNServeEngine(st, max_batch=BATCH, mode="subgraph",
                            pipeline_depth=2, multi_bucket=True,
                            tracer=SpanTracer(sample_every=1))
    qm = engine.submit_many("g", "gcn", nodes)
    engine.run_until_drained()
    assert all(q.done for q in qm)
    n_batches = len(engine.batch_log)
    assert n_batches > 1
    # fewer dispatches than batches — the co-launch actually coalesced
    assert engine.dispatch_count < n_batches
    assert engine.dispatch_count < serial.dispatch_count
    assert serial.dispatch_count == len(serial.batch_log)
    # span evidence: coalesced launch spans share one dispatch window
    launches = [s for tr in engine.tracer.batch_traces() for s in tr.spans
                if s.name == "launch"]
    co = [s for s in launches if s.attrs.get("coalesced", 1) > 1]
    assert co, "no coalesced launch spans recorded"
    windows = {}
    for s in co:
        windows.setdefault((s.t0, s.t1), []).append(s)
    for (t0, t1), spans in windows.items():
        assert len(spans) == spans[0].attrs["coalesced"]
    # bit-exactness: replay the actual compositions against a fresh session
    oracle = _store(data).session("g", "gcn")
    for batch in engine.batch_log:
        want = oracle.serve_subgraph(np.asarray([q.node for q in batch]))
        np.testing.assert_array_equal(
            np.stack([q.logits for q in batch]), want)
    assert engine.snapshot()["multi_bucket"] is True


def test_launch_many_bitwise_vs_serial(data):
    """Core-level guarantee under every family: ``launch_many`` of K staged
    buckets returns bitwise what K serial ``launch`` calls return (the
    co-launched program is the serial bodies unrolled), and counts as ONE
    dispatch and at most one extra trace."""
    for model in ["gcn", "sage", "saint"]:
        sess = _store(data).session("g", model)
        rng = np.random.default_rng(3)
        b1 = sess.prepare_batch(rng.integers(0, data.n_nodes, size=BATCH))
        b2 = sess.prepare_batch(rng.integers(0, data.n_nodes, size=4))
        core = sess.core
        s1 = core.launch(b1.groups[0].staged, b1.bn)
        s2 = core.launch(b2.groups[0].staged, b2.bn)
        d0 = core.n_dispatches
        m1, m2 = core.launch_many([(b1.groups[0].staged, b1.bn),
                                   (b2.groups[0].staged, b2.bn)])
        assert core.n_dispatches == d0 + 1
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(m1))
        np.testing.assert_array_equal(np.asarray(s2), np.asarray(m2))


# ------------------------------------------------------------ tuner cache ---

def test_tuner_cache_roundtrip_and_lookup(tmp_path):
    from repro.serve.tuner_cache import SCHEMA, TunerCache, entry_key
    path = tmp_path / "cache.json"
    cache = TunerCache(path)
    stats = dict(n_nodes=100, n_edges=400, n_feat=32)
    cache.record(stats, (8, 64), 2e-3, fused=False, backend="cpu")
    cache.record(stats, None, 1e-3, fused=False, backend="cpu")
    cache.record(stats, (4, 32), 3e-3, fused=True, backend="cpu")
    # fastest wins per (stats, backend, fused); default block stays None
    reloaded = TunerCache(path)
    assert reloaded.lookup(stats, fused=False, backend="cpu") is None
    assert reloaded.lookup(stats, fused=True, backend="cpu") == (4, 32)
    # different stats or backend: no entry
    assert reloaded.lookup(dict(stats, n_nodes=101), backend="cpu") is None
    assert reloaded.lookup(stats, fused=False, backend="tpu") is None
    assert entry_key(stats, (8, 64), "cpu", False) in reloaded.entries
    # unknown schema is ignored, not migrated
    path.write_text('{"schema": 999, "entries": {"x": {}}}')
    assert TunerCache(path).entries == {}
    # corrupt file is ignored too
    path.write_text("not json")
    assert TunerCache(path).entries == {}


def test_graphstore_seeds_block_from_tuner_cache(tmp_path, data):
    """A store given a tuner cache seeds SessionPlan.bspmm_block from the
    fastest recorded block for the graph's stats fingerprint; an explicit
    store-level block override wins over the cache."""
    from repro.serve.tuner_cache import TunerCache, graph_stats
    path = tmp_path / "cache.json"
    cache = TunerCache(path)
    cache.record(graph_stats(data), (8, 64), 1e-3, fused=False,
                 backend=jax.default_backend())
    cache.record(graph_stats(data), (4, 32), 9e-3, fused=False,
                 backend=jax.default_backend())
    st = _store(data, use_pallas=True, tuner_cache=str(path))
    assert st.session("g", "gcn").plan.bspmm_block == (8, 64)
    # explicit override beats the cache
    st2 = _store(data, use_pallas=True, tuner_cache=str(path),
                 bspmm_block=(4, 32))
    assert st2.session("g", "gcn").plan.bspmm_block == (4, 32)
    # no cache entry for other stats: kernel-native default
    other = make_dataset("cora", seed=1, scale=0.05)
    st3 = GraphStore(max_batch=BATCH, use_pallas=True,
                     tuner_cache=str(path))
    st3.register_graph("g", other)
    st3.register_model("gcn", "gcn", gnn.init_gcn(
        jax.random.PRNGKey(0), other.x.shape[1], HIDDEN, other.n_classes))
    assert st3.session("g", "gcn").plan.bspmm_block is None


# -------------------------------------------------------- plan persistence --

def test_session_plan_fused_roundtrip(data, tmp_path):
    """SessionPlan.fused survives the artifact JSON roundtrip, shows in the
    plan name, and a store with a different fused flag REBUILDS instead of
    loading a mismatched artifact."""
    from repro.serve.session_core import SessionPlan
    p = SessionPlan("gcn", "bin", fused=True)
    p2 = SessionPlan.from_json(p.to_json())
    assert p2.fused and "|fused" in p2.name()
    assert not SessionPlan.from_json(
        SessionPlan("gcn", "bin").to_json()).fused

    st1 = _store(data, cache_dir=str(tmp_path), use_pallas=True, fused=True)
    assert st1.session("g", "gcn").plan.fused
    # same flag: loads; different flag: rebuilds with the requested flag
    st2 = _store(data, cache_dir=str(tmp_path), use_pallas=True, fused=True)
    assert st2.session("g", "gcn").plan.fused
    st3 = _store(data, cache_dir=str(tmp_path), use_pallas=True)
    assert not st3.session("g", "gcn").plan.fused


def test_fused_refused_on_tpu_at_session_build(data, monkeypatch):
    """``fused=True`` cannot compile for a TPU (no grid, whole arrays in
    VMEM, gathers inside the kernel): on a TPU backend the session build
    raises a clear error instead of failing at first launch and retrying
    until the queries are shed. Interpret mode keeps it runnable here."""
    monkeypatch.setattr(kernel_ops, "_on_tpu", lambda: True)
    st = _store(data, use_pallas=True, fused=True)
    with pytest.raises(NotImplementedError, match="fused"):
        st.session("g", "gcn")
    with pytest.raises(NotImplementedError, match="fused"):
        st.sharded_session("g", "gcn", 2)
    # the unfused Pallas path builds on the same backend
    assert not _store(data, use_pallas=True).session("g", "gcn").plan.fused


# ------------------------------------------------------------- env helper ---

def test_compile_cache_dir_env_helper(tmp_path):
    """The persistent compile cache lives where JAX_COMPILATION_CACHE_DIR
    says; without it at one fixed path inside the checkout (the path is
    part of every cache key, so it must not move)."""
    from pathlib import Path
    from repro import env
    got, from_env = env.compile_cache_dir({env.ENV_VAR: str(tmp_path)})
    assert (got, from_env) == (tmp_path, True)
    got, from_env = env.compile_cache_dir({})
    checkout = Path(env.__file__).resolve().parents[2]
    assert not from_env
    assert got == checkout / ".jax_cache" == env.DEFAULT_DIR
    assert (checkout / "src" / "repro" / "env.py").exists()
    assert env.compile_cache_dir({env.ENV_VAR: ""}) == (got, False)
