"""Serving telemetry tests: span tracing, watchdogs, exporters, the
perf-regression gate, and the metrics satellites.

The acceptance bar (ISSUE 6): a pipelined sharded serve (P=2) produces a
Chrome-trace JSON whose spans reconstruct per-batch extract/compute/
queue-wait within 1ms of the ``ServeMetrics`` stage sums; the recompile
watchdog fires on a forced novel shape and stays silent across 2 feature
updates in steady state; ``compare_bench.py`` exits nonzero on a synthetic
2x p99 regression and zero on identical inputs; tracing at the default
sampling stays within 5% of the untraced QPS.
"""
import copy
import json
import time

import numpy as np
import jax
import pytest

from repro.graphs.datasets import make_dataset
from repro.models import gnn
from repro.serve import (GNNServeEngine, GraphStore, LatencyStats,
                         ServeMetrics, ShardedServeEngine, SpanTracer,
                         chrome_trace, prometheus_text, write_chrome_trace)
from repro.serve.session_core import StagedBatch
from repro.serve.trace import STAGES, BatchTrace, TransferWatchdog

jax.config.update("jax_platform_name", "cpu")

HIDDEN = 16
BATCH = 8
PIPELINE_DEPTH = 2


@pytest.fixture(scope="module")
def data():
    return make_dataset("cora", seed=0, scale=0.1)


@pytest.fixture(scope="module")
def store(data):
    st = GraphStore(max_batch=BATCH)
    st.register_graph("g", data)
    st.register_model("gcn", "gcn",
                      gnn.init_gcn(jax.random.PRNGKey(0), data.x.shape[1],
                                   HIDDEN, data.n_classes))
    return st


def _serve(engine, data, n=64, seed=0):
    engine.warmup("g", "gcn")
    nodes = np.random.default_rng(seed).integers(0, data.n_nodes, size=n)
    qs = engine.submit_many("g", "gcn", nodes)
    engine.run_until_drained()
    assert all(q.done for q in qs)
    return qs


# ------------------------------------------------------------ acceptance ---

def test_sharded_p2_trace_reconstructs_metrics(store, data, tmp_path):
    """Pipelined sharded serve at P=2: the recorded span tree reconstructs
    the per-batch extract / attributed-compute / queue-wait stage sums
    within 1ms of what ``ServeMetrics`` accumulated, and the Chrome-trace
    export is a loadable span-per-track JSON."""
    engine = ShardedServeEngine(store, 2, max_batch=BATCH, mode="subgraph",
                                pipeline_depth=PIPELINE_DEPTH,
                                staleness_s=600.0,
                                tracer=SpanTracer(sample_every=1))
    _serve(engine, data)
    m = engine.metrics
    trs = engine.tracer.batch_traces()
    assert len(trs) == m.batches          # sample_every=1 records them all
    assert all(t.kept for t in trs)

    ext = sum(t.stage_s("extract") for t in trs)
    cmp_ = sum(t.stage_s("compute") for t in trs)   # attributed_s sums
    assert abs(ext - m.extract_s) < 1e-3
    assert abs(cmp_ - m.compute_s) < 1e-3
    # per-query queue waits are non-negative and end at the pick time
    for t in trs:
        for q in t.queries:
            assert q["queue_wait_s"] >= 0.0
        (qw,) = [s for s in t.spans if s.name == "queue_wait"]
        assert qw.t1 == t.t_start
    # every batch is tagged with its owning shard and tenant
    assert {t.shard for t in trs} <= {0, 1}
    assert {t.tenant for t in trs} == {"default"}
    # halo attribution from the static schedule rode along
    assert all("serve_x_bytes" in t.halo for t in trs)

    path = tmp_path / "trace.json"
    obj = write_chrome_trace(engine.tracer, str(path))
    loaded = json.loads(path.read_text())
    assert loaded == obj
    events = [e for e in loaded["traceEvents"] if e["ph"] == "X"]
    assert len(events) == sum(len(t.spans) for t in trs)
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in events)
    # one track (pid) per shard, one thread per pipeline stage
    meta = [e for e in loaded["traceEvents"] if e["ph"] == "M"]
    pnames = {e["args"]["name"] for e in meta
              if e["name"] == "process_name"}
    assert pnames == {"shard-0", "shard-1"}
    tnames = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert set(STAGES) <= tnames
    engine.close()


def test_recompile_watchdog_silent_then_fires(data):
    """Steady state across 2 feature updates: zero watchdog events. A
    forced novel shape (bucket watermark doubled behind the engine's back):
    the watchdog fires with the offending shape key."""
    st = GraphStore(max_batch=BATCH)
    st.register_graph("g", data)
    st.register_model("gcn", "gcn",
                      gnn.init_gcn(jax.random.PRNGKey(0), data.x.shape[1],
                                   HIDDEN, data.n_classes))
    engine = ShardedServeEngine(st, 2, max_batch=BATCH, mode="subgraph",
                                pipeline_depth=PIPELINE_DEPTH,
                                staleness_s=600.0)
    _serve(engine, data)
    assert engine.recompile_watchdog.armed
    rng = np.random.default_rng(1)
    for i in (1, 2):                      # two steady-state feature updates
        st.update_features("g", data.x + np.float32(1e-3 * i))
        engine.submit_many("g", "gcn",
                           rng.integers(0, data.n_nodes, size=2 * BATCH))
        engine.run_until_drained()
    assert engine.recompile_watchdog.steady_recompiles == 0
    assert engine.tracer.warning_events() == []

    # force a novel launch shape: doubling the node watermark guarantees a
    # never-traced pow2 bucket on the next prepared batch
    sess = st.sharded_session("g", "gcn", 2)
    for core in sess.cores:
        core._n_water *= 2
    engine.submit_many("g", "gcn",
                       rng.integers(0, data.n_nodes, size=2 * BATCH))
    engine.run_until_drained()
    assert engine.recompile_watchdog.steady_recompiles > 0
    events = engine.tracer.warning_events()
    assert events and all(e.name == "recompile" for e in events)
    assert all("core" in e.attrs["label"] for e in events)
    assert all(e.attrs["shape"]["n_pad"] > 0 for e in events)
    engine.close()


def test_compare_bench_gate(tmp_path):
    """Identical inputs exit 0; a synthetic 2x p99 regression exits 1."""
    import sys
    sys.path.insert(0, str((__import__("pathlib").Path(__file__)
                            .resolve().parents[1])))
    from benchmarks.compare_bench import main

    base = dict(schema_version=2, families=dict(gcn=dict(subgraph=dict(
        qps=2500.0, steady_state_compiles=0,
        latency=dict(count=200, p50_ms=5.0, p99_ms=7.0)))))
    pb = tmp_path / "base.json"
    pb.write_text(json.dumps(base))
    assert main([str(pb), str(pb)]) == 0

    bad = copy.deepcopy(base)
    bad["families"]["gcn"]["subgraph"]["latency"]["p99_ms"] *= 2
    pc = tmp_path / "bad.json"
    pc.write_text(json.dumps(bad))
    assert main([str(pb), str(pc)]) == 1

    # warn band: 1.5x p99 warns but passes — unless --strict
    warn = copy.deepcopy(base)
    warn["families"]["gcn"]["subgraph"]["latency"]["p99_ms"] *= 1.5
    pw = tmp_path / "warn.json"
    pw.write_text(json.dumps(warn))
    assert main([str(pb), str(pw)]) == 0
    assert main([str(pb), str(pw), "--strict"]) == 1

    # zero-tolerance: any steady-state compile increase fails outright
    cmp_ = copy.deepcopy(base)
    cmp_["families"]["gcn"]["subgraph"]["steady_state_compiles"] = 1
    pz = tmp_path / "compiles.json"
    pz.write_text(json.dumps(cmp_))
    assert main([str(pb), str(pz)]) == 1


def test_trace_overhead_within_5pct(store, data):
    """Steady-state serve with tracing at the default sampling stays within
    5% of the untraced QPS. Runs are INTERLEAVED traced/untraced pairs and
    each side takes its best-of-5, so a noisy host window (the full suite
    running around this test) degrades both sides instead of one."""
    def qps_once(trace):
        engine = GNNServeEngine(store, max_batch=BATCH, mode="subgraph",
                                pipeline_depth=PIPELINE_DEPTH, trace=trace)
        _serve(engine, data, n=192, seed=3)
        q = engine.snapshot()["qps"]
        engine.close()
        return q

    qps_once(True)                        # common warm pass (jit, caches)
    qps_once(False)
    pairs = [(qps_once(True), qps_once(False)) for _ in range(5)]
    traced = max(t for t, _ in pairs)
    untraced = max(u for _, u in pairs)
    assert traced >= 0.95 * untraced, (traced, untraced)


# -------------------------------------------------------------- tracer -----

def _dummy_trace(tracer, key=("g", "m", "default"), total_s=0.01):
    t0 = time.perf_counter()
    tr = tracer.begin(key, key[-1], None, [], t0)
    if tr is not None:
        tr.t_end = t0 + total_s
    return tr


def test_ring_buffer_wraparound():
    tracer = SpanTracer(capacity=4, sample_every=1)
    for _ in range(10):
        tracer.commit(_dummy_trace(tracer))
    recs = tracer.records()
    assert len(recs) == 4                  # bounded
    assert tracer.batches_seen == 10
    assert tracer.batches_recorded == 10   # all were recorded, ring kept 4
    ids = [r.trace_id for r in recs]
    assert ids == sorted(ids) and ids[-1] == 9   # oldest-first, newest kept


def test_sampling_one_in_n():
    tracer = SpanTracer(sample_every=4)
    kept = sum(tracer.commit(_dummy_trace(tracer)) for _ in range(16))
    assert kept == 4                       # batches 0, 4, 8, 12


def test_outliers_always_recorded():
    tracer = SpanTracer(sample_every=10**9)
    for _ in range(64):                    # build the rolling p99 window
        tracer.commit(_dummy_trace(tracer, total_s=0.01))
    assert tracer.commit(_dummy_trace(tracer, total_s=10.0))
    assert tracer.outliers_recorded == 1
    assert tracer.batch_traces()[-1].kept == "outlier"


def test_error_requeue_always_sampled(store, data):
    """A compute failure commits the batch's trace on the error path even
    with sampling effectively off (reuses the PR 4 failure-injection
    hook)."""
    engine = GNNServeEngine(store, max_batch=BATCH, mode="subgraph",
                            pipeline_depth=PIPELINE_DEPTH,
                            tracer=SpanTracer(sample_every=10**9))
    engine.warmup("g", "gcn")
    session = engine._get_session(("g", "gcn"))
    real = session.launch_batch
    calls = {"n": 0}

    def flaky(*args):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient compute failure")
        return real(*args)

    session.launch_batch = flaky
    try:
        qs = engine.submit_many("g", "gcn", np.arange(BATCH))
        with pytest.raises(RuntimeError, match="transient"):
            engine.run_until_drained()
        errors = [t for t in engine.tracer.batch_traces() if t.error]
        assert len(errors) == 1
        assert errors[0].kept == "error"
        assert errors[0].requeued
        assert "transient compute failure" in errors[0].error
        engine.run_until_drained()         # retry succeeds
    finally:
        session.launch_batch = real
    assert all(q.done for q in qs)
    assert engine.tracer.errors_recorded == 1
    engine.close()


def test_transfer_watchdog_flags_host_sync(store, data):
    """A launch that returns concrete host arrays (a blocking
    device->host sync inside the dispatch) is counted and emitted as a
    structured warning; the clean engine path counts zero."""
    engine = GNNServeEngine(store, max_batch=BATCH, mode="subgraph")
    _serve(engine, data, n=2 * BATCH)
    assert engine.transfer_watchdog.host_sync_in_launch == 0
    assert engine.transfer_watchdog.device_in_extract == 0

    session = engine._get_session(("g", "gcn"))
    real = session.launch_batch
    session.launch_batch = lambda prep: [np.asarray(d)
                                         for d in real(prep)]
    try:
        engine.submit_many("g", "gcn", np.arange(BATCH))
        engine.run_until_drained()
    finally:
        session.launch_batch = real
    assert engine.transfer_watchdog.host_sync_in_launch > 0
    warns = [e for e in engine.tracer.warning_events()
             if e.name == "transfer"]
    assert warns and warns[0].attrs["kind"] == "host_sync_in_launch"
    engine.close()


def test_queries_carry_trace_context(store, data):
    """Served queries link back to the batch trace that answered them, and
    the trace records the scheduler's virtual-time tag at pick."""
    engine = GNNServeEngine(store, max_batch=BATCH, mode="subgraph",
                            tracer=SpanTracer(sample_every=1))
    qs = _serve(engine, data, n=4 * BATCH)
    ids = {t.trace_id for t in engine.tracer.batch_traces()}
    assert all(q.trace_id in ids for q in qs)
    vtimes = [t.vtime for t in engine.tracer.batch_traces()]
    assert vtimes == sorted(vtimes) and vtimes[-1] > 0   # advancing vtime
    engine.close()


# ------------------------------------------------------------ exporters ----

def test_prometheus_text(store, data):
    engine = GNNServeEngine(store, max_batch=BATCH, mode="subgraph")
    _serve(engine, data)
    txt = prometheus_text(engine.snapshot(), engine.tracer)
    assert txt.endswith("\n")
    # every series is namespaced by the engine's model family
    assert 'serve_queries_total{family="gnn"} 64' in txt
    assert 'serve_latency_ms{family="gnn",group="query",quantile="p99"}' \
        in txt
    assert 'serve_tenant_accepted_total{family="gnn",tenant="default"} 64' \
        in txt
    assert "serve_trace_batches_seen_total" in txt
    # every sample line parses as <name>{labels} <float>
    for line in txt.splitlines():
        if line.startswith("#"):
            continue
        name, val = line.rsplit(" ", 1)
        float(val)
    engine.close()


def test_chrome_trace_empty_and_warnings_only():
    tracer = SpanTracer()
    assert chrome_trace(tracer)["traceEvents"] == []
    tracer.warning("recompile", label="core", shape=dict(n_pad=64))
    obj = chrome_trace(tracer)
    inst = [e for e in obj["traceEvents"] if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["name"] == "recompile"


# ------------------------------------------------------ metrics satellites --

def test_latency_stats_window_vs_count():
    ls = LatencyStats(max_samples=4)
    for i in range(10):
        ls.record(i * 1e-3)
    s = ls.summary()
    assert s["count"] == 10                # lifetime
    assert s["window"] == 4 == ls.window   # retained ring
    assert s["max_ms"] == pytest.approx(9.0)
    empty = LatencyStats().summary()
    assert empty["count"] == 0 and empty["window"] == 0


def test_serve_metrics_clock_restart_safe():
    """A second serve wave after stop_clock() must RESUME the clock: the
    banked first-wave time is kept, elapsed keeps growing, and qps is
    total queries over total serving time."""
    m = ServeMetrics()
    m.start_clock()
    time.sleep(0.02)
    m.stop_clock()
    wave1 = m.elapsed_s
    assert wave1 >= 0.02
    time.sleep(0.02)
    assert m.elapsed_s == wave1            # stopped clock holds
    m.start_clock()                        # second wave resumes
    time.sleep(0.02)
    m.stop_clock()
    assert m.elapsed_s >= wave1 + 0.02
    m.queries = 100
    assert m.qps == pytest.approx(100 / m.elapsed_s)
    # idempotent start while running (the engine calls it per submit)
    m2 = ServeMetrics()
    m2.start_clock()
    t0 = m2.started_at
    m2.start_clock()
    assert m2.started_at == t0


def test_engine_two_wave_qps_not_inflated(store, data):
    """Engine-level regression: serve, drain (stop_clock), pause, serve
    again — elapsed_s must cover both waves, so qps cannot be inflated by
    the frozen first-wave window."""
    engine = GNNServeEngine(store, max_batch=BATCH, mode="subgraph")
    _serve(engine, data, n=2 * BATCH)
    e1 = engine.metrics.elapsed_s
    time.sleep(0.05)                        # idle gap: must not count
    nodes = np.random.default_rng(7).integers(0, data.n_nodes,
                                              size=2 * BATCH)
    engine.submit_many("g", "gcn", nodes)
    engine.run_until_drained()
    e2 = engine.metrics.elapsed_s
    assert e2 > e1                          # second wave extended the clock
    assert e2 < e1 + 0.05                   # ... but not by the idle gap
    assert engine.metrics.queries == 4 * BATCH
    assert engine.metrics.qps == pytest.approx(4 * BATCH / e2)
    engine.close()


# ------------------------------------------------------------- watchdogs ---

def test_transfer_watchdog_unit():
    class G:
        def __init__(self, x):
            self.staged = StagedBatch(x_pad=x, adjs={},
                                      pos_pad=np.zeros(1, np.int32),
                                      n_seeds=1)

    class P:
        def __init__(self, xs):
            self.groups = [G(x) for x in xs]

    wd = TransferWatchdog(SpanTracer())
    wd.check_prepared(P([np.zeros((4, 4))]))
    assert wd.device_in_extract == 0
    import jax.numpy as jnp
    wd.check_prepared(P([jnp.zeros((4, 4))]))   # device-resident staged
    assert wd.device_in_extract == 1
    wd.check_launched([jnp.zeros((4,))])
    assert wd.host_sync_in_launch == 0
    wd.check_launched([np.zeros((4,))])         # host array out of launch
    assert wd.host_sync_in_launch == 1
    assert {e.attrs["kind"] for e in wd.tracer.warning_events()} == \
        {"device_in_extract", "host_sync_in_launch"}


def test_tracer_disabled_is_noop(store, data):
    engine = GNNServeEngine(store, max_batch=BATCH, mode="subgraph",
                            trace=False)
    _serve(engine, data, n=2 * BATCH)
    assert engine.tracer.records() == []
    assert engine.tracer.batches_seen == 0
    snap = engine.snapshot()
    assert snap["trace"]["enabled"] is False
    engine.close()


# ------------------------------------------- spans on the profiler clock ---

# the parent each serve.* span has (a sync runs under the full-cache
# lookup, or under a subgraph batch's extract)
SPAN_PARENT = {
    "serve.tick": (None,),
    "serve.pick": ("serve.tick",),
    "serve.extract": ("serve.tick",),
    "serve.lookup": ("serve.extract",),
    "serve.sync": ("serve.lookup", "serve.extract"),
    "serve.sync.upload": ("serve.sync",),
    "serve.sync.forward": ("serve.sync",),
    "serve.sync.copyback": ("serve.sync",),
    "serve.extract.khop": ("serve.extract",),
    "serve.extract.frdc": ("serve.extract",),
    "serve.extract.stage": ("serve.extract",),
    "serve.launch": ("serve.tick",),
    "serve.finish": ("serve.tick",),
    "serve.complete": ("serve.tick",),
    "serve.deliver": ("serve.complete",),
}
# what each serve path runs in a tick that follows a feature update
PATH_SPANS = {
    "full": set(SPAN_PARENT) - {"serve.finish", "serve.extract.khop",
                                "serve.extract.frdc",
                                "serve.extract.stage"},
    "subgraph": set(SPAN_PARENT) - {"serve.lookup"},
}


@pytest.fixture()
def own_store():
    """A store over a graph of its own: these tests update features."""
    d = make_dataset("cora", seed=1, scale=0.1)
    st = GraphStore(max_batch=BATCH)
    st.register_graph("g", d)
    st.register_model("gcn", "gcn",
                      gnn.init_gcn(jax.random.PRNGKey(0), d.x.shape[1],
                                   HIDDEN, d.n_classes))
    return st


def test_spans_table_is_the_program_list():
    from repro.serve.trace import SPANS
    assert set(SPANS) == set(SPAN_PARENT)
    assert len(SPANS) == len(set(SPANS))


@pytest.mark.parametrize("mode", ["full", "subgraph"])
def test_program_spans_nest_on_profiler_clock(own_store, mode, tmp_path):
    """Under a profiler, one feature update and a few batches leave every
    span of the path in the trace, each under the parent it belongs to,
    and no serve.* name outside ``SPANS``."""
    from jax.profiler import ProfileData
    from chipbench import spans
    from repro.serve.trace import SPANS

    engine = GNNServeEngine(own_store, max_batch=BATCH, mode=mode,
                            tracer=SpanTracer(sample_every=1))
    engine.warmup("g", "gcn")
    x = own_store.graphs["g"].data.x
    jax.profiler.start_trace(str(tmp_path))
    try:
        own_store.update_features("g", x + 1.0)
        qs = engine.submit_many("g", "gcn", np.arange(3 * BATCH))
        engine.run_until_drained()
    finally:
        jax.profiler.stop_trace()
    assert all(q.done for q in qs)
    (path,) = sorted(tmp_path.rglob("*.xplane.pb"))
    got = spans.from_profile(ProfileData.from_file(str(path)))
    names = {s.name for s in got}
    assert names <= set(SPANS), names - set(SPANS)
    assert names == PATH_SPANS[mode]
    for s, p in zip(got, spans.parents(got)):
        parent = None if p is None else got[p].name
        assert parent in SPAN_PARENT[s.name], (s.name, parent)
    # one sync, and the ring still holds its stages
    assert sum(s.name == "serve.sync" for s in got) == 1
    assert {e.name for t in engine.tracer.batch_traces()
            for e in t.spans} == set(STAGES)
    engine.close()


@pytest.mark.parametrize("incremental", [False, True])
def test_sync_counts_feature_upload_bytes(incremental):
    d = make_dataset("cora", seed=2, scale=0.1)
    st = GraphStore(max_batch=BATCH, incremental=incremental)
    st.register_graph("g", d)
    st.register_model("gcn", "gcn",
                      gnn.init_gcn(jax.random.PRNGKey(0), d.x.shape[1],
                                   HIDDEN, d.n_classes))
    session = st.session("g", "gcn")
    b0 = session.bytes_uploaded
    assert b0 == d.x.nbytes                 # the build's first sync
    session.sync()                          # current: no upload
    assert session.bytes_uploaded == b0
    for k in (1, 2):
        x = d.x.copy()
        x[:k] += 1.0
        st.update_features("g", x)
        session.sync()
        assert session.bytes_uploaded == b0 + k * x.nbytes
    engine = GNNServeEngine(st, max_batch=BATCH, mode="full")
    engine.submit("g", "gcn", 0)
    engine.run_until_drained()
    assert engine.snapshot()["bytes_uploaded"]["sync"] == 3 * d.x.nbytes


def test_launch_counts_host_bytes(store, data):
    """ServeCore.bytes_uploaded rises per launch by the bytes of every host
    array handed to the jitted forward, for one bucket and a co-launch. A
    single-host batch hands over row ids, not features: the features it
    gathers stay on the device and are not counted."""
    session = store.session("g", "gcn")
    core = session.core
    rng = np.random.default_rng(5)
    preps = [session.prepare_batch(rng.integers(0, data.n_nodes, BATCH))
             for _ in range(2)]

    def host_bytes(staged):
        adj = sum(a.nbytes for m in staged.adjs.values()
                  for a in m.values() if isinstance(a, np.ndarray))
        return staged.rows.nbytes + staged.pos_pad.nbytes + adj

    sizes = [host_bytes(p.groups[0].staged) for p in preps]
    s0 = preps[0].groups[0].staged
    assert s0.x_pad is None and s0.rows.shape == (s0.n_pad,)
    assert sizes[0] > s0.rows.nbytes
    assert sizes[0] < s0.n_pad * data.x.shape[1] * data.x.itemsize
    b0 = core.bytes_uploaded
    devs = session.launch_batch(preps[0])
    session.finish_batch(preps[0], devs)
    assert core.bytes_uploaded == b0 + sizes[0]
    outs = core.launch_many([(p.groups[0].staged, p.bn) for p in preps])
    jax.block_until_ready(outs)
    assert core.bytes_uploaded == b0 + 2 * sizes[0] + sizes[1]


def test_forward_program_names_match_the_benchmark():
    """The benchmark finds the forwards in a device trace by the names XLA
    gives their programs (``chipbench/record.py``); a rename of the jitted
    functions has to fail here, not silently empty its device metrics."""
    import re
    import sys
    from pathlib import Path
    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench.record import FULL_FORWARD, SERVE_FORWARD

    d = make_dataset("cora", seed=3, scale=0.1)
    st = GraphStore(max_batch=BATCH)
    st.register_graph("g", d)
    st.register_model("gcn", "gcn",
                      gnn.init_gcn(jax.random.PRNGKey(0), d.x.shape[1],
                                   HIDDEN, d.n_classes))
    session = st.session("g", "gcn")
    x = jax.numpy.asarray(d.x)
    s = session.prepare_batch(np.arange(BATCH)).groups[0].staged
    args = s.launch_args(session.bn)
    core = session.core
    lowered = {
        FULL_FORWARD: [session._jit_full.lower(x),
                       session._jit_full_frozen.lower(x, session.bn)],
        SERVE_FORWARD: [core._jit_serve.lower(*args),
                        core._jit_serve_many.lower((args, args))]}
    for pattern, lows in lowered.items():
        for low in lows:
            name = re.match(r"module @(\S+)", low.as_text()).group(1)
            assert re.search(pattern, name), (pattern, name)
