"""Serving benchmark: QPS + latency percentiles of the GNN serving subsystem
(GraphStore -> CompiledGraphSession -> GNNServeEngine) on a stat-matched
synthetic Table-2 graph, for all three model families and both serve paths
(micro-batched k-hop subgraph vs. cached full-graph inference).

Queries arrive in waves (submit one micro-batch worth, then tick) so the
reported latency is end-to-end batch service time, not closed-loop queueing
over the whole run. Emits CSV rows like every other section plus a
``results/BENCH_serve_gnn.json`` summary — the start of the serving-side
perf trajectory (kernels are tracked by the other sections).
"""
from __future__ import annotations

import gc
import json
from pathlib import Path

import jax
import numpy as np

from repro.graphs.datasets import make_dataset
from repro.models import gnn
from repro.serve import (AdmissionController, CostEstimator, GNNServeEngine,
                         GraphStore, SLOPolicy, SLOTracker, TenantPolicy,
                         prometheus_text, spearman_rho)

from .common import csv_row

RESULTS = Path(__file__).resolve().parents[1] / "results"

# bump when the emitted JSON layout changes (compare_bench.py warns on
# cross-version diffs). v3: cost-model + SLO leaves (the ``slo`` section,
# ``cost_spearman_rho``). v4: the ``kernels`` section (fused-vs-unfused
# launch footprint + per-layer latency, multi-bucket dispatch reduction).
SCHEMA_VERSION = 4

FAMILY_INITS = {
    "gcn": gnn.init_gcn, "sage": gnn.init_sage, "saint": gnn.init_saint,
}
N_LAYERS = {"gcn": 2, "sage": 2, "saint": 3}


def _serve_wave(engine: GNNServeEngine, graph: str, model: str,
                nodes: np.ndarray, batch: int) -> None:
    for i in range(0, nodes.size, batch):
        engine.submit_many(graph, model, nodes[i:i + batch])
        engine.tick()
    engine.run_until_drained()


def _bench_mode(store: GraphStore, family: str, mode: str, n_queries: int,
                n_nodes: int, batch: int, seed: int = 0,
                pipeline_depth: int = 0) -> dict:
    engine = GNNServeEngine(store, max_batch=batch, mode=mode,
                            pipeline_depth=pipeline_depth)
    warm_compiles = engine.warmup("bench", family)
    c0 = engine.compile_count
    nodes = np.random.default_rng(seed).integers(0, n_nodes, size=n_queries)
    # a collector pass landing inside a sub-ms full-cache wave dominates its
    # p99, and WHICH wave it lands in shifts with the process's unrelated
    # allocation history — pause the collector for the measured waves (same
    # idiom as the SLO section's calibration loop)
    gc.collect()
    gc_was = gc.isenabled()
    gc.disable()
    try:
        _serve_wave(engine, "bench", family, nodes, batch)
    finally:
        if gc_was:
            gc.enable()
    snap = engine.snapshot()
    snap["warmup_compiles"] = warm_compiles
    snap["steady_state_compiles"] = engine.compile_count - c0
    engine.close()
    return snap


def _bench_tenants(store: GraphStore, family: str, n_nodes: int,
                   batch: int, n_good: int, seed: int = 0) -> dict:
    """Two-tenant overload scenario: ``hog`` submits 10x the well-behaved
    ``good`` tenant's volume against a rate limit + queue-depth bound, so
    most of its traffic comes back typed (shed at the depth bound while
    tokens remain, throttled once the bucket drains). Records the admission
    outcomes, the weighted fairness of what WAS admitted, and the good
    tenant's p99 against its own solo run — the acceptance gauge is
    ``good_p99_within_2x_solo``."""
    rng = np.random.default_rng(seed)
    good_nodes = rng.integers(0, n_nodes, size=n_good)
    policies = dict(
        good=TenantPolicy(weight=8),
        hog=TenantPolicy(rate_qps=5.0, burst=batch,
                         max_queue_depth=batch, weight=1),
    )

    def one_run(with_hog: bool) -> dict:
        engine = GNNServeEngine(
            store, max_batch=batch, mode="subgraph",
            admission=AdmissionController(policies=dict(policies)))
        engine.warmup("bench", family)
        for i in range(0, good_nodes.size, batch):
            engine.submit_many("bench", family, good_nodes[i:i + batch],
                               tenant="good")
            if with_hog:                 # 10x the good tenant's volume
                hog_nodes = rng.integers(0, n_nodes, size=10 * batch)
                engine.submit_many("bench", family, hog_nodes, tenant="hog")
            # two service slots per arrival wave: the engine has the
            # capacity to absorb the hog's ADMITTED trickle, so the good
            # tenant's p99 reflects scheduling, not an undersized server
            engine.tick()
            engine.tick()
        engine.run_until_drained()
        snap = engine.snapshot()
        engine.close()
        return snap

    solo = one_run(False)
    mixed = one_run(True)
    good, hog = mixed["tenants"]["good"], mixed["tenants"]["hog"]
    p99_solo = solo["tenants"]["good"]["latency"]["p99_ms"]
    p99_mixed = good["latency"]["p99_ms"]
    def _fin(v):                       # inf -> null (strict-JSON safe)
        return None if v is None or np.isinf(v) else v

    return dict(
        family=family,
        policy={t: dict(rate_qps=_fin(p.rate_qps),
                        burst=_fin(p.bucket_capacity),
                        weight=p.weight, max_queue_depth=p.max_queue_depth)
                for t, p in policies.items()},
        good_solo=solo["tenants"]["good"],
        good_mixed=good,
        hog_mixed=hog,
        hog_shed_rate=hog["shed_rate"],
        hog_reject_rate=hog["reject_rate"],
        fairness_served_ratio=(good["queries"] / max(hog["queries"], 1)),
        good_p99_solo_ms=p99_solo,
        good_p99_mixed_ms=p99_mixed,
        good_p99_ratio=p99_mixed / max(p99_solo, 1e-9),
        good_p99_within_2x_solo=bool(p99_mixed <= 2.0 * p99_solo),
    )


def _degree_bands(store: GraphStore, graph: str, n_bands: int = 4):
    """Node-id bands stratified by degree (ascending): the calibration
    stream serves degree-homogeneous waves so per-batch predicted cost
    actually VARIES — a uniformly random stream averages every batch to the
    same cost and leaves rank correlation nothing to rank."""
    csr = store.graphs[graph].csr
    degs = np.asarray(csr.indptr[1:]) - np.asarray(csr.indptr[:-1])
    order = np.argsort(degs, kind="stable")
    return np.array_split(order, n_bands)


def _replay_bit_exact(store: GraphStore, graph: str, family: str,
                      engine: GNNServeEngine) -> bool:
    """The batch_log oracle: replay the cost-aware engine's actual served
    batch compositions straight through the raw session — cost-weighted
    scheduling may REORDER service, but every answer must be bit-identical
    to the cost-unaware compute path."""
    sess = store.session(graph, family)
    for batch in engine.batch_log:
        seeds = np.asarray([q.node for q in batch], np.int64)
        prepared = sess.prepare_batch(seeds)
        logits = sess.finish_batch(prepared, sess.launch_batch(prepared))
        got = np.stack([q.logits for q in batch])
        if not np.array_equal(np.asarray(logits), got):
            return False
    return True


def _kernel_path_stats(store: GraphStore, family: str,
                       seeds: np.ndarray, repeats: int) -> tuple:
    """Serve one bucketed batch through ``store``'s kernel path and measure
    its launch footprint: the traced-program equation/pallas counts of the
    ACTUAL jitted forward (via ``ops.launch_stats`` on the staged operands),
    the fused trace-time kernel counter, and a best-of-``repeats`` forward
    latency. Returns (stats dict, logits) — logits so the caller can assert
    fused/unfused bitwise identity."""
    import time

    from repro.kernels import fused_layer
    from repro.kernels import ops as kernel_ops
    from repro.serve import session_core

    sess = store.session("bench", family)
    fused_layer.reset_counters()
    logits = np.asarray(sess.serve_subgraph(seeds))       # warmup + trace
    fused_calls = fused_layer.KERNEL_CALLS["fused"]
    prepared = sess.prepare_batch(np.asarray(seeds, np.int64))
    g = prepared.groups[0]
    x, *args = g.staged.launch_args(prepared.bn)
    # count the layers' operations: the input-row gather is not a layer's
    tr = kernel_ops.launch_stats(g.core._serve_one,
                                 session_core.forward_input(x), *args)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        np.asarray(sess.serve_subgraph(seeds))
        best = min(best, time.perf_counter() - t0)
    n_layers = N_LAYERS[family]
    stats = dict(
        plan=sess.plan.name(),
        pallas_launches=tr["pallas_calls"],
        traced_ops=tr["eqns"],
        ops_per_layer=tr["eqns"] / n_layers,
        fused_kernel_calls=fused_calls,
        latency_ms=best * 1e3,
        layer_latency_ms=best * 1e3 / n_layers,
    )
    return stats, logits


def _bench_kernels(d, batch: int, hidden: int, repeats: int = 3) -> dict:
    """Fused-vs-unfused kernel path comparison, per family: the traced
    launch footprint (the fused path collapses each layer's whole op chain
    into ONE ``pallas_call``), per-layer latency both ways, and the bitwise
    identity of their outputs. Forces the kernels on (CPU runs them in
    interpret mode — latency here tracks regressions of the kernel path
    itself, the launch counts are backend-independent trace facts)."""
    from repro.kernels import ops as kernel_ops

    seeds = np.random.default_rng(7).integers(0, d.n_nodes, size=batch)
    out: dict = dict(note="interpret-mode kernels (CPU); launch counts are "
                          "trace-time facts, latencies gate the kernel "
                          "path's own regressions", families={})
    kernel_ops.force_kernels(True)
    try:
        for fam in FAMILY_INITS:
            per: dict = {}
            logits = {}
            for tag, fused in (("unfused", False), ("fused", True)):
                store = GraphStore(max_batch=batch, use_pallas=True,
                                   fused=fused)
                store.register_graph("bench", d)
                store.register_model(
                    fam, fam, FAMILY_INITS[fam](jax.random.PRNGKey(0),
                                                d.x.shape[1], hidden,
                                                d.n_classes))
                per[tag], logits[tag] = _kernel_path_stats(
                    store, fam, seeds, repeats)
            n_layers = N_LAYERS[fam]
            per["n_layers"] = n_layers
            per["launches_per_layer_fused"] = (
                per["fused"]["fused_kernel_calls"] / n_layers)
            per["op_reduction"] = (per["unfused"]["ops_per_layer"]
                                   / max(per["fused"]["ops_per_layer"], 1e-9))
            per["bit_exact"] = bool(
                np.array_equal(logits["fused"], logits["unfused"]))
            out["families"][fam] = per
    finally:
        kernel_ops.force_kernels(False)
    return out


def _bench_multi_bucket(store: GraphStore, family: str, n_nodes: int,
                        batch: int, n_queries: int, depth: int = 3,
                        seed: int = 2) -> dict:
    """Serial vs multi-bucket co-launch on the identical query stream: the
    coalesced engine serves several padded buckets per pipeline tick as ONE
    device dispatch (``ServeCore.launch_many``), so its dispatch count
    drops below one-per-batch while every answer stays bit-identical to
    the serial path (the replayed ``batch_log`` oracle)."""
    nodes = np.random.default_rng(seed).integers(0, n_nodes, size=n_queries)

    def one(multi: bool, measured: bool = True) -> tuple:
        if measured:
            # warm pass on a throwaway engine: the co-launch compositions'
            # ``_serve_many`` traces live on the store's ServeCores, so the
            # measured pass below runs pure steady state for BOTH paths
            one(multi, measured=False)
        engine = GNNServeEngine(store, max_batch=batch, mode="subgraph",
                                pipeline_depth=depth, multi_bucket=multi)
        engine.warmup("bench", family)
        # the store's sessions (and their dispatch counters) outlive each
        # engine — count only THIS run's steady-state dispatches
        d0 = engine.dispatch_count
        engine.submit_many("bench", family, nodes)
        engine.run_until_drained()
        snap = engine.snapshot()
        n_batches = len(engine.batch_log)
        disp = engine.dispatch_count - d0
        replay = (_replay_bit_exact(store, "bench", family, engine)
                  if measured else True)
        engine.close()
        return snap, disp, n_batches, replay

    s_snap, s_disp, s_nb, s_ok = one(False)
    m_snap, m_disp, m_nb, m_ok = one(True)
    return dict(
        family=family, pipeline_depth=depth,
        n_batches_serial=s_nb, n_batches_multi=m_nb,
        serial_dispatches=s_disp, coalesced_dispatches=m_disp,
        dispatch_reduction=s_disp / max(m_disp, 1),
        qps_serial=s_snap["qps"], qps_multi=m_snap["qps"],
        replay_bit_exact=bool(s_ok and m_ok),
    )


def _kernels_rows(section: dict, suffix: str = "") -> None:
    """THE csv emitters of the kernels section (shared by ``run()`` and
    ``--kernels``)."""
    for fam, per in section["families"].items():
        csv_row(f"serve_gnn/kernels/{fam}",
                per["fused"]["latency_ms"] * 1e3,
                f"ops_per_layer_unfused={per['unfused']['ops_per_layer']:.1f};"
                f"ops_per_layer_fused={per['fused']['ops_per_layer']:.1f};"
                f"launches_per_layer_fused="
                f"{per['launches_per_layer_fused']:.2f};"
                f"op_reduction={per['op_reduction']:.1f}x;"
                f"layer_ms_unfused={per['unfused']['layer_latency_ms']:.2f};"
                f"layer_ms_fused={per['fused']['layer_latency_ms']:.2f};"
                f"bit_exact={per['bit_exact']}")
    mb = section["multi_bucket"]
    csv_row("serve_gnn/kernels/multi_bucket", 0.0,
            f"batches={mb['n_batches_multi']};"
            f"serial_dispatches={mb['serial_dispatches']};"
            f"coalesced_dispatches={mb['coalesced_dispatches']};"
            f"dispatch_reduction={mb['dispatch_reduction']:.2f}x;"
            f"replay_bit_exact={mb['replay_bit_exact']}"
            f"{suffix}")


def _bench_slo(store: GraphStore, family: str, n_nodes: int, batch: int,
               n_good: int, seed: int = 0) -> dict:
    """Closed-loop cost/SLO scenario, two parts.

    **Calibration**: a single-tenant serial engine serves a graded cost
    sweep — a leaf anchor plus hub-band batches in pow2 sizes up to a full
    whale batch — so predicted per-batch units and measured service seconds
    both spread. Each fixed composition is served ``reps`` times
    (interleaved) and the gate ranks per-composition BEST-OF times (min,
    like ``timeit`` — scheduler/GC spikes only ever add time) so host
    timing noise can't shuffle adjacent ranks; the raw every-batch rho
    stays as ``rho_raw``. The gate is the Spearman rank correlation of
    the best-of times (``cost_spearman_rho``).

    **Overload**: tenant ``hub`` submits hub-band nodes at a QPS it is
    nominally ALLOWED — but its predicted cost-unit flow exceeds its
    ``cost_rate`` budget, so admission throttles it on cost
    (``hub_cost_throttled``). Its rejections burn its error budget, the
    multi-window burn alert fires into the span tracer (and the Prometheus
    export), and the SLO autotuner shrinks its effective queue depth. The
    well-behaved ``good`` tenant's p99 must stay within 2x its solo run,
    and the replayed ``batch_log`` oracle must stay bit-exact."""
    rng = np.random.default_rng(seed)
    bands = _degree_bands(store, "bench")
    csr = store.graphs["bench"].csr

    # --- calibration: a graded cost sweep through a costed engine --------
    cal_cost = CostEstimator()
    engine = GNNServeEngine(store, max_batch=batch, mode="subgraph")
    engine.warmup("bench", family)
    leaf_band, hub_band = bands[0], bands[-1]
    comps = [rng.choice(leaf_band, size=min(2, leaf_band.size),
                        replace=False).astype(np.int64)]
    s = 2
    while s <= batch:
        comps.append(rng.choice(hub_band, size=min(s, hub_band.size),
                                replace=False).astype(np.int64))
        s *= 2
    # prime the bucket high-water with one whale batch, then two throwaway
    # cycles (estimator detached) so steady-state timing is what gets ranked
    engine.submit_many("bench", family,
                       rng.choice(hub_band, size=min(batch, hub_band.size),
                                  replace=False))
    engine.tick()
    for _ in range(2):
        for nodes in comps:
            engine.submit_many("bench", family, nodes)
            engine.tick()
    engine.run_until_drained()
    engine.cost = cal_cost
    reps = 9
    gc_was = gc.isenabled()
    gc.disable()
    try:
        for _ in range(reps):
            for nodes in comps:
                engine.submit_many("bench", family, nodes)
                engine.tick()
        engine.run_until_drained()
    finally:
        if gc_was:
            gc.enable()
    pred, meas = cal_cost.predicted_vs_measured()
    # best-of-reps (min, like timeit): scheduler/GC spikes only ever ADD
    # time, so the fastest rep is the faithful per-composition cost
    pred_med = np.median(pred.reshape(reps, len(comps)), axis=0)
    meas_med = np.min(meas.reshape(reps, len(comps)), axis=0)
    rho = spearman_rho(pred_med, meas_med)
    raw = cal_cost.rank_correlation()
    calibration = dict(batches_observed=cal_cost.batches_observed,
                       compositions=[int(c.size) for c in comps],
                       reps=reps,
                       rho=(None if rho != rho else float(rho)),
                       rho_raw=(None if raw != raw else float(raw)),
                       estimator=cal_cost.snapshot())
    engine.close()

    # --- overload: cost-budgeted hub tenant vs a well-behaved tenant ----
    hub_band = bands[-1]
    probe = CostEstimator()
    hub_units = float(np.mean([
        probe.estimate("bench", n, csr, khop=2).units
        for n in rng.choice(hub_band, size=min(16, hub_band.size),
                            replace=False)]))
    good_nodes = rng.integers(0, n_nodes, size=n_good)
    policies = dict(
        good=TenantPolicy(weight=8),
        # generous QPS (never binds) — the COST budget is what holds:
        # ~3 hub-scale queries of burst, ~3 hub queries/s sustained
        hub=TenantPolicy(rate_qps=500.0, burst=500,
                         max_queue_depth=2 * batch, weight=1,
                         cost_rate=3.0 * hub_units,
                         cost_burst=3.0 * hub_units),
    )
    slo_policies = dict(
        hub=SLOPolicy(availability=0.99, window_s=4.0, short_window_s=0.5,
                      burn_alert=2.0),
        good=SLOPolicy(availability=0.999, window_s=4.0),
    )

    def one_run(with_hub: bool) -> tuple:
        eng = GNNServeEngine(
            store, max_batch=batch, mode="subgraph",
            admission=AdmissionController(policies=dict(policies)),
            cost=CostEstimator(),
            slo=SLOTracker(dict(slo_policies)))
        eng.warmup("bench", family)
        for i in range(0, good_nodes.size, batch):
            eng.submit_many("bench", family, good_nodes[i:i + batch],
                            tenant="good")
            if with_hub:          # hub-band whales, 2x the good volume
                hub_nodes = rng.choice(hub_band,
                                       size=min(2 * batch, hub_band.size),
                                       replace=False)
                eng.submit_many("bench", family, hub_nodes, tenant="hub")
            # three service slots per arrival wave: capacity for the good
            # batch plus the hub's cost-admitted trickle, so good-tenant
            # p99 reflects scheduling rather than an undersized server
            eng.tick()
            eng.tick()
            eng.tick()
        eng.run_until_drained()
        snap = eng.snapshot()
        return eng, snap

    solo_eng, solo = one_run(False)
    solo_eng.close()
    eng, mixed = one_run(True)
    good, hub = mixed["tenants"]["good"], mixed["tenants"]["hub"]
    p99_solo = solo["tenants"]["good"]["latency"]["p99_ms"]
    p99_mixed = good["latency"]["p99_ms"]
    slo_hub = mixed["slo"]["tenants"]["hub"]
    burn_warnings = [w for w in eng.tracer.warning_events()
                     if w.name == "slo_burn"]
    prom = prometheus_text(mixed, eng.tracer)
    replay_ok = _replay_bit_exact(store, "bench", family, eng)
    eng.close()

    return dict(
        family=family,
        cost_spearman_rho=calibration["rho"],
        calibration=calibration,
        policy=dict(hub_cost_rate=policies["hub"].cost_rate,
                    hub_probe_units=hub_units),
        good_solo=solo["tenants"]["good"],
        good_mixed=good,
        hub_mixed=hub,
        hub_cost_throttled=hub["cost_throttled"],
        hub_held_to_cost_budget=bool(hub["cost_throttled"] > 0),
        hub_slo=slo_hub,
        burn_alerts_fired=len(burn_warnings),
        burn_alert_in_trace=bool(burn_warnings),
        burn_alert_in_prometheus=(
            'serve_slo_alerts_total{tenant="hub"}' in prom
            and slo_hub["alerts"] > 0),
        depth_autotuned=bool(slo_hub["depth_shrinks"] > 0),
        good_p99_solo_ms=p99_solo,
        good_p99_mixed_ms=p99_mixed,
        good_p99_ratio=p99_mixed / max(p99_solo, 1e-9),
        good_p99_within_2x_solo=bool(p99_mixed <= 2.0 * p99_solo),
        replay_bit_exact=replay_ok,
    )


def _slo_row(section: dict, suffix: str = "") -> None:
    """THE csv emitter of the slo section (shared by ``run()`` and
    ``--slo``)."""
    rho = section["cost_spearman_rho"]
    csv_row("serve_gnn/slo",
            section["good_p99_mixed_ms"] * 1e3,
            f"rho={-1.0 if rho is None else rho:.3f};"
            f"hub_cost_throttled={section['hub_cost_throttled']};"
            f"burn_alerts={section['burn_alerts_fired']};"
            f"depth_autotuned={section['depth_autotuned']};"
            f"p99_ratio={section['good_p99_ratio']:.2f};"
            f"within_2x={section['good_p99_within_2x_solo']};"
            f"replay_bit_exact={section['replay_bit_exact']}"
            f"{suffix}")


def _tenants_row(section: dict, suffix: str = "") -> None:
    """THE csv emitter of the tenants section — shared by ``run()`` and the
    standalone ``--tenants`` entry so the row never drifts between them."""
    csv_row("serve_gnn/tenants",
            section["good_p99_mixed_ms"] * 1e3,
            f"good_p99_solo_ms={section['good_p99_solo_ms']:.2f};"
            f"good_p99_mixed_ms={section['good_p99_mixed_ms']:.2f};"
            f"p99_ratio={section['good_p99_ratio']:.2f};"
            f"within_2x={section['good_p99_within_2x_solo']};"
            f"hog_reject_rate={section['hog_reject_rate']:.2f};"
            f"hog_shed_rate={section['hog_shed_rate']:.2f};"
            f"hog_accepted={section['hog_mixed']['accepted']}"
            f"{suffix}")


def _merge_results(section: str, payload: dict) -> Path:
    """Write ``payload`` under ``section`` of BENCH_serve_gnn.json, keeping
    whatever other sections a previous (possibly fuller) run recorded."""
    RESULTS.mkdir(parents=True, exist_ok=True)
    out = RESULTS / "BENCH_serve_gnn.json"
    summary = json.loads(out.read_text()) if out.exists() else {}
    summary.setdefault("schema_version", SCHEMA_VERSION)
    summary[section] = payload
    out.write_text(json.dumps(summary, indent=2))
    return out


def run_slo(full: bool = False) -> dict:
    """Standalone ``--slo`` entry: cost calibration + the cost-budget/SLO
    overload scenario only, merged into the existing results JSON."""
    scale = 1.0 if full else 0.15
    batch = 32 if full else 16
    hidden = 64 if full else 32
    n_good = 320 if full else 96

    d = make_dataset("cora", seed=0, scale=scale)
    store = GraphStore(max_batch=batch)
    store.register_graph("bench", d)
    store.register_model("gcn", "gcn",
                         gnn.init_gcn(jax.random.PRNGKey(0), d.x.shape[1],
                                      hidden, d.n_classes))
    section = _bench_slo(store, "gcn", d.n_nodes, batch, n_good)
    out = _merge_results("slo", section)
    _slo_row(section, suffix=f";wrote={out}")
    return section


def run_tenants(full: bool = False) -> dict:
    """Standalone ``--tenants`` entry: the overload scenario only, merged
    into the existing results JSON."""
    scale = 1.0 if full else 0.15
    batch = 32 if full else 16
    hidden = 64 if full else 32
    n_good = 320 if full else 96

    d = make_dataset("cora", seed=0, scale=scale)
    store = GraphStore(max_batch=batch)
    store.register_graph("bench", d)
    store.register_model("gcn", "gcn",
                         gnn.init_gcn(jax.random.PRNGKey(0), d.x.shape[1],
                                      hidden, d.n_classes))
    section = _bench_tenants(store, "gcn", d.n_nodes, batch, n_good)
    out = _merge_results("tenants", section)
    _tenants_row(section, suffix=f";wrote={out}")
    return section


def run_kernels(full: bool = False) -> dict:
    """Standalone ``--kernels`` entry: the fused-vs-unfused launch footprint
    and the multi-bucket co-launch comparison only, merged into the
    existing results JSON."""
    scale = 1.0 if full else 0.15
    batch = 32 if full else 16
    hidden = 64 if full else 32

    d = make_dataset("cora", seed=0, scale=scale)
    section = _bench_kernels(d, batch, hidden)
    store = GraphStore(max_batch=batch)
    store.register_graph("bench", d)
    store.register_model("gcn", "gcn",
                         gnn.init_gcn(jax.random.PRNGKey(0), d.x.shape[1],
                                      hidden, d.n_classes))
    section["multi_bucket"] = _bench_multi_bucket(
        store, "gcn", d.n_nodes, batch, n_queries=6 * batch)
    out = _merge_results("kernels", section)
    _kernels_rows(section, suffix=f";wrote={out}")
    return section


def run(full: bool = False) -> dict:
    scale = 1.0 if full else 0.15
    n_queries = 1000 if full else 200
    batch = 32 if full else 16
    hidden = 64 if full else 32

    d = make_dataset("cora", seed=0, scale=scale)
    store = GraphStore(max_batch=batch)
    store.register_graph("bench", d)
    key = jax.random.PRNGKey(0)
    for fam, init in FAMILY_INITS.items():
        store.register_model(fam, fam, init(key, d.x.shape[1], hidden,
                                            d.n_classes))

    summary: dict = dict(schema_version=SCHEMA_VERSION, dataset="cora",
                         scale=scale, n_nodes=d.n_nodes,
                         n_edges=d.n_edges, n_queries=n_queries,
                         batch=batch, families={})
    for fam in FAMILY_INITS:
        sess = store.session("bench", fam, tune=(fam == "gcn"),
                             tune_repeats=1)
        fam_out = dict(plan=sess.plan.name(),
                       tuned_latency_ms=sess.plan.tuned_latency_s * 1e3)
        for mode in ("subgraph", "full"):
            snap = _bench_mode(store, fam, mode, n_queries, d.n_nodes, batch)
            fam_out[mode] = snap
            lat = snap["latency"]
            csv_row(f"serve_gnn/{fam}/{mode}",
                    1e6 / max(snap["qps"], 1e-9),
                    f"qps={snap['qps']:.1f};p50_ms={lat['p50_ms']:.2f};"
                    f"p99_ms={lat['p99_ms']:.2f};"
                    f"hit_rate={snap['cache_hit_rate']:.2f};"
                    f"steady_compiles={snap['steady_state_compiles']}")
        # the pipelined subgraph loop: extraction of batch i+1 overlaps the
        # in-flight forward of batch i (bit-exact vs the serial rows above)
        snap = _bench_mode(store, fam, "subgraph", n_queries, d.n_nodes,
                           batch, pipeline_depth=2)
        fam_out["subgraph_pipelined"] = snap
        bd = snap["batch_breakdown"]
        csv_row(f"serve_gnn/{fam}/subgraph_pipelined",
                1e6 / max(snap["qps"], 1e-9),
                f"qps={snap['qps']:.1f};"
                f"overlap={snap['overlap_ratio']:.2f};"
                f"extract_p50_ms={bd['extract']['p50_ms']:.2f};"
                f"compute_p50_ms={bd['compute']['p50_ms']:.2f};"
                f"steady_compiles={snap['steady_state_compiles']}")
        summary["families"][fam] = fam_out

    # the multi-tenant overload scenario (fairness + shed-rate + the good
    # tenant's p99-vs-solo acceptance gauge)
    summary["tenants"] = _bench_tenants(
        store, "gcn", d.n_nodes, batch,
        n_good=(320 if full else 96))
    _tenants_row(summary["tenants"])

    # cost calibration + the cost-budget/SLO closed-loop overload scenario
    summary["slo"] = _bench_slo(store, "gcn", d.n_nodes, batch,
                                n_good=(320 if full else 96))
    _slo_row(summary["slo"])

    # fused-vs-unfused kernel launch footprint + multi-bucket co-launch
    summary["kernels"] = _bench_kernels(d, batch, hidden)
    summary["kernels"]["multi_bucket"] = _bench_multi_bucket(
        store, "gcn", d.n_nodes, batch, n_queries=6 * batch)
    _kernels_rows(summary["kernels"])

    RESULTS.mkdir(parents=True, exist_ok=True)
    out = RESULTS / "BENCH_serve_gnn.json"
    out.write_text(json.dumps(summary, indent=2))
    csv_row("serve_gnn/summary", 0.0, f"wrote={out}")
    return summary


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--tenants", action="store_true",
                    help="run only the multi-tenant overload scenario and "
                    "merge it into results/BENCH_serve_gnn.json")
    ap.add_argument("--slo", action="store_true",
                    help="run only the cost/SLO closed-loop scenario and "
                    "merge it into results/BENCH_serve_gnn.json")
    ap.add_argument("--kernels", action="store_true",
                    help="run only the fused-vs-unfused launch footprint + "
                    "multi-bucket co-launch comparison and merge it into "
                    "results/BENCH_serve_gnn.json")
    args = ap.parse_args()
    if args.tenants:
        run_tenants(full=args.full)
    elif args.slo:
        run_slo(full=args.full)
    elif args.kernels:
        run_kernels(full=args.full)
    else:
        run(full=args.full)
