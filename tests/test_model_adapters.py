"""ModelFamilyAdapter conformance: the contract every family behind the
serving core must honour, exercised against all three adapters (GNN /
transformer / SSM):

  * kind labels — the metrics/trace namespace each adapter claims;
  * bucket invariance — ``pad_operands`` water marks are monotone pow2,
    so staging order (not launch order) keys the jit cache;
  * zero steady-state recompiles — after warmup, varied batch shapes
    never re-trace;
  * prepared-batch pinning — an extracted batch finishes under the
    core/params/state it was staged for, across hot swaps;
  * injected launch failures flow through the engine's requeue/retry
    path and the queries still complete correctly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.graphs.datasets import make_dataset
from repro.models import gnn, transformer
from repro.serve import (FaultInjector, GNNAdapter, GNNServeEngine,
                         GraphStore, InjectedFault, SessionPlan,
                         TokenAdapter, TokenServeEngine, TokenSession,
                         TokenStore)

jax.config.update("jax_platform_name", "cpu")

TOKEN_ARCHS = {"transformer": "stablelm-1.6b", "ssm": "rwkv6-3b"}


def _token_cfg(name):
    return reduced_config(get_config(name)).resolve_for_mesh(tp=1)


def _token_session(name, **kw):
    cfg = _token_cfg(name)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 128)
    kw.setdefault("chunk", 4)
    kw.setdefault("warm_len", 6)
    kw.setdefault("warm_new", 4)
    return TokenSession("s", cfg, params, **kw)


@pytest.fixture(scope="module")
def gnn_store():
    data = make_dataset("cora", seed=0, scale=0.1)
    st = GraphStore(max_batch=4)
    st.register_graph("g", data)
    st.register_model("gcn", "gcn",
                      gnn.init_gcn(jax.random.PRNGKey(0),
                                   data.x.shape[1], 16, data.n_classes))
    return st


# --------------------------------------------------------------- kinds ----
def test_adapter_kind_labels():
    assert GNNAdapter(SessionPlan(family="gcn", scheme="bmm")).kind == "gnn"
    assert TokenAdapter(_token_cfg("stablelm-1.6b")).kind == "transformer"
    assert TokenAdapter(_token_cfg("rwkv6-3b")).kind == "ssm"
    # hybrids decode through the recurrent path -> namespaced as ssm
    assert TokenAdapter(_token_cfg("zamba2-1.2b")).kind == "ssm"
    with pytest.raises(ValueError):
        TokenAdapter(_token_cfg("seamless-m4t-medium"))


# ------------------------------------------------------ bucket shaping ----
@pytest.mark.parametrize("kind", sorted(TOKEN_ARCHS))
def test_token_bucket_water_monotone(kind):
    """Cache-length buckets only grow: a smaller batch after a large one
    reuses the established pow2 water (same jit key), and exceeding the
    session cap raises instead of silently truncating the decode."""
    s = _token_session(TOKEN_ARCHS[kind])
    core, adapter = s.core, s.adapter
    n1, _ = adapter.pad_operands(core, {}, 40)
    assert n1 == 64 and core._n_water == 64          # pow2, floor 64
    n2, _ = adapter.pad_operands(core, {}, 5)
    assert n2 == n1                                   # water holds
    n3, _ = adapter.pad_operands(core, {}, 65)
    assert n3 == 128 and core._n_water == 128         # monotone growth
    with pytest.raises(ValueError):
        adapter.pad_operands(core, {}, s.max_len + 1)


def test_gnn_bucket_water_monotone(gnn_store):
    """Same invariant on the GNN adapter: a small batch staged after a big
    one pads to the big batch's node bucket."""
    sess = gnn_store.session("g", "gcn")
    rng = np.random.default_rng(0)
    big = sess.prepare_batch(rng.integers(0, 100, size=4))
    n_big = big.groups[0].staged.n_pad
    small = sess.prepare_batch(rng.integers(0, 100, size=1))
    assert small.groups[0].staged.n_pad == n_big
    assert n_big == 2 ** int(np.log2(n_big))


# ------------------------------------------- zero steady-state recompiles --
@pytest.mark.parametrize("kind", sorted(TOKEN_ARCHS))
def test_token_zero_steady_state_recompiles(kind):
    """After warmup sets the cache-length water, batches of every size /
    prompt length / decode budget under it hit the one compiled program."""
    s = _token_session(TOKEN_ARCHS[kind], warm_len=10, warm_new=8)
    rng = np.random.default_rng(0)
    assert s.warmup(rng) >= 1
    c0 = s.compile_count
    for n, ln, mn in [(1, 3, 2), (2, 9, 7), (2, 1, 1), (1, 10, 8)]:
        prompts = [rng.integers(0, s.cfg.vocab, ln).astype(np.int32)
                   for _ in range(n)]
        outs = s.run(prompts, [mn] * n)
        assert all(o.size == mn for o in outs)
    assert s.compile_count == c0


def test_gnn_zero_steady_state_recompiles(gnn_store):
    sess = gnn_store.session("g", "gcn")
    rng = np.random.default_rng(1)
    sess.warmup(rng)
    c0 = sess.compile_count
    for n in (1, 4, 2):
        sess.serve_subgraph(rng.integers(0, 100, size=n))
    assert sess.compile_count == c0


# ------------------------------------------------------ prepared pinning --
@pytest.mark.parametrize("kind", sorted(TOKEN_ARCHS))
def test_token_prepared_batch_pins_params(kind):
    """An in-flight prepared batch finishes under the params it was staged
    for: ``update_params`` swaps the session's core, but the prepared
    groups keep the old core (and its packed weights) pinned."""
    s = _token_session(TOKEN_ARCHS[kind])
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, s.cfg.vocab, 5).astype(np.int32),
               rng.integers(0, s.cfg.vocab, 3).astype(np.int32)]
    mns = [4, 6]
    want = s.run(prompts, mns)
    prepared = s.prepare_batch(prompts, mns)    # staged under OLD params
    s.update_params(transformer.init_params(jax.random.PRNGKey(7), s.cfg))
    assert s.invalidations == 1
    got = s.finish_batch(prepared, s.launch_batch(prepared))
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    fresh = s.run(prompts, mns)                 # NEW params: streams differ
    assert any(not np.array_equal(f, w) for f, w in zip(fresh, want))


def test_gnn_prepared_batch_pins_features(gnn_store):
    """The GNN twin: staged features/calibration are pinned, so a feature
    update between stage and launch does not leak into the batch."""
    sess = gnn_store.session("g", "gcn")
    seeds = np.array([3, 17, 41])
    want = sess.serve_subgraph(seeds)
    prepared = sess.prepare_batch(seeds)
    # flip feature signs — the binarized forward quantizes inputs, so only
    # a sign change is guaranteed to alter the served logits
    x2 = -(gnn_store.graphs["g"].data.x + 1.0)
    gnn_store.update_features("g", x2)
    got = sess.finish_batch(prepared, sess.launch_batch(prepared))
    np.testing.assert_array_equal(got, want)
    after = sess.serve_subgraph(seeds)          # fresh stage sees new x
    assert not np.array_equal(after, want)


# -------------------------------------------------- failure -> requeue ----
def test_token_injected_launch_failure_requeues():
    """A launch-stage fault flows through the engine's requeue/retry path:
    the queries retry, complete, and the streams match a clean session."""
    cfg = _token_cfg("stablelm-1.6b")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    store = TokenStore(max_batch=2, max_len=128, chunk=4,
                       warm_len=6, warm_new=4)
    store.register_model("lm", cfg, params)
    fi = FaultInjector(seed=0)
    eng = TokenServeEngine(store, faults=fi, retry_backoff_s=0.0)
    eng.warmup("lm")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, 4).astype(np.int32)
               for _ in range(3)]
    fi.fail_next("launch", 1)
    qs = eng.submit_many("lm", prompts, max_new=3)
    # the failing tick requeues the batch at the front of its queue and
    # re-raises (never-lose-queries); the next drain retries and completes
    with pytest.raises(InjectedFault):
        eng.run_until_drained()
    eng.run_until_drained()
    eng.close()
    assert all(q.done for q in qs)
    assert max(q.attempts for q in qs) >= 1   # the faulted batch retried
    clean = TokenSession("ref", cfg, params, max_batch=2, max_len=128,
                         chunk=4)
    for q, p in zip(qs, prompts):
        assert np.array_equal(q.tokens, clean.run([p], [3])[0])


def test_gnn_injected_launch_failure_requeues(gnn_store):
    fi = FaultInjector(seed=0)
    eng = GNNServeEngine(gnn_store, mode="subgraph", faults=fi,
                         retry_backoff_s=0.0)
    want = gnn_store.session("g", "gcn").serve_subgraph(np.array([5, 9]))
    fi.fail_next("launch", 1)
    qs = eng.submit_many("g", "gcn", np.array([5, 9]))
    with pytest.raises(InjectedFault):
        eng.run_until_drained()
    eng.run_until_drained()
    eng.close()
    assert all(q.done for q in qs)
    assert max(q.attempts for q in qs) >= 1   # the faulted batch retried
    np.testing.assert_array_equal(np.stack([q.logits for q in qs]), want)
