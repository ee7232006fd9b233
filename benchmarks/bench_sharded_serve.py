"""Sharded serving benchmark: QPS + latency percentiles of the partitioned
engine (ShardPlanner -> ShardedGraphSession -> ShardedServeEngine) against
the single-host baseline, plus the halo traffic the distributed pass and the
routed subgraph path moved — per layer, packed vs fp.

Shards are simulated on one host (the shard boundary, routing and halo
mechanics are identical; only the transport latency is not real), so the QPS
columns measure the ORCHESTRATION overhead of sharding, and the halo-bytes
columns the communication volume a real deployment would pay — the number
the paper's bit-packing shrinks 32x on the binary-aggregation layer.

Two additional sections per family x P:

  * ``full_pass_latency`` — host-orchestrated vs SPMD executor wall time of
    one distributed full pass (``--executor`` picks which executor the
    ENGINE benches use; the comparison always runs both when the host can
    expose P devices — forced via ``ensure_host_devices`` when this module
    runs standalone — and records SPMD/host bit-equality);
  * ``bn_calibration_drift`` — distributed BN calibration (psum moments
    from the pass itself) vs the single-host anchor: max |logit delta| and
    argmax agreement;
  * ``pipeline`` — the double-buffered extract/compute engine with
    halo-aware batch formation vs the strict-FIFO serial engine on the
    identical query stream: overlap ratio, per-stage breakdown, and the
    MEASURED ``serve/x`` halo bytes saved by co-batching seeds that share
    halo tiles (``--pipeline`` additionally switches the main engine
    benches to the pipelined loop).

Emits CSV rows like every other section plus
``results/BENCH_sharded_serve.json``.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.graphs.datasets import make_dataset
from repro.models import gnn
from repro.serve import (AdmissionController, GNNServeEngine, GraphStore,
                         ShardedServeEngine, SpanTracer, TenantPolicy,
                         write_chrome_trace)

from .common import csv_row

RESULTS = Path(__file__).resolve().parents[1] / "results"

# bump when the emitted JSON layout changes (compare_bench.py warns on
# cross-version diffs). v3: sharded snapshots carry ``whale_splits`` (and
# cost/SLO leaves when a CostEstimator/SLOTracker is wired). v4: the
# ``kernels`` section (multi-bucket co-launch dispatch reduction per shard
# count + the fused sharded path's bit-exactness).
SCHEMA_VERSION = 4

FAMILY_INITS = {
    "gcn": gnn.init_gcn, "sage": gnn.init_sage, "saint": gnn.init_saint,
}
SHARD_COUNTS = (2, 4)


def _time_full_pass(sess, repeats: int) -> float:
    sess.run_distributed_pass()                       # warm the programs
    t0 = time.perf_counter()
    for _ in range(repeats):
        sess.run_distributed_pass()
    return (time.perf_counter() - t0) / repeats


def _executor_compare(store, fam: str, p: int, spmd_ok: bool,
                      repeats: int) -> dict:
    """Host-vs-SPMD full-pass latency + SPMD bit-equality check."""
    host = store.sharded_session("bench", fam, p)
    out = dict(host_full_pass_s=_time_full_pass(host, repeats),
               spmd_full_pass_s=None, spmd_bit_exact=None,
               spmd_layer_compiles=None)
    if spmd_ok:
        spmd = store.sharded_session("bench", fam, p, executor="spmd")
        out["spmd_full_pass_s"] = _time_full_pass(spmd, repeats)
        out["spmd_bit_exact"] = bool(np.array_equal(spmd.full_logits(),
                                                    host.full_logits()))
        out["spmd_layer_compiles"] = spmd.executor_compile_count
    return out


def _bn_drift(store, fam: str, p: int, executor: str) -> dict:
    """Distributed-BN serving drift vs the single-host calibration."""
    anchor = store.session("bench", fam).full_logits()
    dist = store.sharded_session("bench", fam, p, executor=executor,
                                 bn_mode="distributed")
    got = dist.full_logits()
    return dict(
        executor=executor,
        max_abs_logit_delta=float(np.abs(got - anchor).max()),
        argmax_agreement=float((np.argmax(got, -1)
                                == np.argmax(anchor, -1)).mean()))


def _serve_wave(engine, graph: str, model: str, nodes: np.ndarray,
                batch: int) -> None:
    for i in range(0, nodes.size, batch):
        engine.submit_many(graph, model, nodes[i:i + batch])
        engine.tick()
    engine.run_until_drained()


def _bench_engine(engine, fam: str, nodes: np.ndarray, batch: int) -> dict:
    warm = engine.warmup("bench", fam)
    c0 = engine.compile_count
    _serve_wave(engine, "bench", fam, nodes, batch)
    snap = engine.snapshot()
    snap["warmup_compiles"] = warm
    snap["steady_state_compiles"] = engine.compile_count - c0
    engine.close()
    return snap


PIPELINE_DEPTH = 2


def _pipeline_compare(store, fam: str, p: int, executor: str,
                      nodes: np.ndarray, batch: int,
                      trace_path=None) -> dict:
    """Pipelined + halo-aware engine vs the strict-FIFO serial engine on
    the identical query stream (submitted up-front so batch formation has a
    real queue to group over): overlap ratio, stage breakdown, and the
    MEASURED ``serve/x`` halo bytes each run actually gathered — the delta
    is what halo-aware co-batching saved. With ``trace_path``, the
    pipelined run records EVERY batch span (sample_every=1) and exports a
    Perfetto-loadable Chrome trace there."""
    sess = store.sharded_session("bench", fam, p, executor=executor)

    def run_one(halo_aware: bool, depth: int, tracer=None):
        engine = ShardedServeEngine(store, p, max_batch=batch,
                                    mode="subgraph", executor=executor,
                                    halo_aware=halo_aware,
                                    pipeline_depth=depth, tracer=tracer)
        engine.warmup("bench", fam)
        c0 = engine.compile_count
        b0 = sess.halo_stats.bytes_by_tag.get("serve/x", 0)
        engine.submit_many("bench", fam, nodes)
        engine.run_until_drained()
        moved = sess.halo_stats.bytes_by_tag.get("serve/x", 0) - b0
        snap = engine.snapshot()
        snap["steady_state_compiles"] = engine.compile_count - c0
        engine.close()
        return snap, moved

    tracer = SpanTracer(sample_every=1) if trace_path is not None else None
    fifo_snap, fifo_bytes = run_one(False, 0)
    aware_snap, aware_bytes = run_one(True, PIPELINE_DEPTH, tracer=tracer)
    if tracer is not None:
        write_chrome_trace(tracer, str(trace_path))
        csv_row(f"sharded_serve/{fam}/P{p}/trace", 0.0,
                f"spans={len(tracer.batch_traces())};wrote={trace_path}")
    return dict(
        pipeline_depth=PIPELINE_DEPTH,
        overlap_ratio=aware_snap["overlap_ratio"],
        batch_breakdown=aware_snap["batch_breakdown"],
        qps_fifo_serial=fifo_snap["qps"],
        qps_pipelined=aware_snap["qps"],
        serve_x_bytes_fifo=fifo_bytes,
        serve_x_bytes_halo_aware=aware_bytes,
        halo_bytes_saved_measured=fifo_bytes - aware_bytes,
        halo_bytes_saved_est=aware_snap["halo_bytes_saved"],
        halo_tiles_shared=aware_snap["halo_tiles_shared"],
        steady_state_compiles=aware_snap["steady_state_compiles"],
    )


def _bench_tenants_sharded(store, fam: str, p: int, executor: str,
                           n_nodes: int, batch: int,
                           n_queries: int) -> dict:
    """Weighted two-tenant wave through the sharded engine: tenancy keys
    ride inside the (owner, tenant) queues, so every served batch stays
    single-owner AND single-tenant; records the per-tenant breakdown and
    the served ratio against the 4:1 weights."""
    admission = AdmissionController(
        policies={"gold": TenantPolicy(weight=4),
                  "base": TenantPolicy(weight=1)})
    engine = ShardedServeEngine(store, p, max_batch=batch, mode="subgraph",
                                executor=executor, admission=admission)
    engine.warmup("bench", fam)
    rng = np.random.default_rng(3)
    nodes = rng.integers(0, n_nodes, size=n_queries)
    for i, n in enumerate(nodes):
        engine.submit("bench", fam, n,
                      tenant=("gold" if i % 2 else "base"))
    engine.run_until_drained()
    snap = engine.snapshot()
    mixed = sum(len({q.tenant for q in b}) != 1 for b in engine.batch_log)
    engine.close()
    return dict(n_shards=p, weights=dict(gold=4, base=1),
                tenants=snap["tenants"], tenant_mixed_batches=mixed)


def _multi_bucket_compare(store, fam: str, p: int, executor: str,
                          nodes: np.ndarray, batch: int) -> dict:
    """Serial vs multi-bucket co-launch through the sharded engine: with
    coalescing on, each pump tick dispatches every core's share of the
    formed batches as ONE ``launch_many`` program per core, so the dispatch
    count drops below one-per-batch. Every answer is replayed through a
    single-host session (the ``batch_log`` oracle) — co-launching and
    sharding together must stay bit-identical to the unsharded forward."""
    oracle = store.session("bench", fam)
    # sharded queues alternate owner shards, so a pump tick only holds >= 2
    # batches of the SAME core once the pipeline is ~2 batches deep per
    # shard — scale the depth with the shard count
    depth = 2 * p

    def one(multi: bool, measured: bool = True) -> tuple:
        if measured:        # warm the co-launch composition traces first
            one(multi, measured=False)
        engine = ShardedServeEngine(store, p, max_batch=batch,
                                    mode="subgraph", executor=executor,
                                    pipeline_depth=depth,
                                    multi_bucket=multi)
        engine.warmup("bench", fam)
        d0 = engine.dispatch_count
        engine.submit_many("bench", fam, nodes)
        engine.run_until_drained()
        snap = engine.snapshot()
        n_batches = len(engine.batch_log)
        disp = engine.dispatch_count - d0
        replay = measured and all(
            np.array_equal(
                np.stack([q.logits for q in b]),
                np.asarray(oracle.serve_subgraph(
                    np.asarray([q.node for q in b], np.int64))))
            for b in engine.batch_log)
        engine.close()
        return snap, disp, n_batches, replay

    s_snap, s_disp, s_nb, s_ok = one(False)
    m_snap, m_disp, m_nb, m_ok = one(True)
    return dict(
        n_shards=p, pipeline_depth=depth,
        n_batches_serial=s_nb, n_batches_multi=m_nb,
        serial_dispatches=s_disp, coalesced_dispatches=m_disp,
        dispatch_reduction=s_disp / max(m_disp, 1),
        qps_serial=s_snap["qps"], qps_multi=m_snap["qps"],
        replay_bit_exact=bool(s_ok and m_ok),
    )


def _fused_sharded_bit_exact(d, fam: str, p: int, batch: int,
                             hidden: int) -> bool:
    """Serve one batch through a FUSED sharded session (kernels forced on,
    interpret mode off-TPU) and compare bitwise against the UNFUSED sharded
    forward — the fused-path half of the sharded bit-exactness acceptance
    (fusing a layer must never change its arithmetic), recorded where the
    gate can see it. The oracle is the sharded unfused path: sharded serving
    itself sits one fp-reassociation ulp from the single-host forward (the
    intra+halo aggregation split), fused or not."""
    from repro.kernels import ops as kernel_ops

    def build(fused: bool) -> GraphStore:
        st = GraphStore(max_batch=batch, use_pallas=True, fused=fused)
        st.register_graph("bench", d)
        st.register_model(fam, fam,
                          FAMILY_INITS[fam](jax.random.PRNGKey(0),
                                            d.x.shape[1], hidden,
                                            d.n_classes))
        return st

    seeds = np.random.default_rng(5).integers(0, d.n_nodes, size=batch)
    kernel_ops.force_kernels(True)
    try:
        want = np.asarray(
            build(False).sharded_session("bench", fam, p)
            .serve_subgraph(seeds))
        got = np.asarray(
            build(True).sharded_session("bench", fam, p)
            .serve_subgraph(seeds))
    finally:
        kernel_ops.force_kernels(False)
    return bool(np.array_equal(got, want))


def run(full: bool = False, executor: str = "host",
        pipeline: bool = False) -> dict:
    # the SPMD comparison needs P host devices; only effective when jax has
    # not initialized a backend yet (standalone runs) — otherwise the SPMD
    # columns degrade to None and the host columns still emit.
    from repro.launch.mesh import ensure_host_devices
    spmd_ok = ensure_host_devices(max(SHARD_COUNTS))
    if executor == "spmd" and not spmd_ok:
        print("# bench_sharded_serve: --executor spmd needs "
              f"{max(SHARD_COUNTS)} devices, have {len(jax.devices())}; "
              "falling back to host for the engine benches")
        executor = "host"
    scale = 1.0 if full else 0.15
    n_queries = 600 if full else 120
    batch = 32 if full else 16
    hidden = 64 if full else 32
    pass_repeats = 5 if full else 2

    d = make_dataset("cora", seed=0, scale=scale)
    store = GraphStore(max_batch=batch)
    store.register_graph("bench", d)
    key = jax.random.PRNGKey(0)
    for fam, init in FAMILY_INITS.items():
        store.register_model(fam, fam, init(key, d.x.shape[1], hidden,
                                            d.n_classes))

    engine_depth = PIPELINE_DEPTH if pipeline else 0
    summary: dict = dict(schema_version=SCHEMA_VERSION, dataset="cora",
                         scale=scale, n_nodes=d.n_nodes,
                         n_edges=d.n_edges, n_queries=n_queries,
                         batch=batch, shard_counts=list(SHARD_COUNTS),
                         engine_executor=executor, spmd_available=spmd_ok,
                         engine_pipeline_depth=engine_depth,
                         families={})
    rng = np.random.default_rng(0)
    nodes = rng.integers(0, d.n_nodes, size=n_queries)

    for fam in FAMILY_INITS:
        fam_out: dict = {}
        single = _bench_engine(
            GNNServeEngine(store, max_batch=batch, mode="subgraph",
                           pipeline_depth=engine_depth),
            fam, nodes, batch)
        fam_out["single"] = single
        csv_row(f"sharded_serve/{fam}/single",
                1e6 / max(single["qps"], 1e-9),
                f"qps={single['qps']:.1f};"
                f"p50_ms={single['latency']['p50_ms']:.2f};"
                f"p99_ms={single['latency']['p99_ms']:.2f}")
        for p in SHARD_COUNTS:
            engine = ShardedServeEngine(store, p, max_batch=batch,
                                        mode="subgraph", executor=executor,
                                        pipeline_depth=engine_depth)
            snap = _bench_engine(engine, fam, nodes, batch)
            sess = store.sharded_session("bench", fam, p,
                                         executor=executor)
            snap["plan_stats"] = sess.shard_plan.stats()
            # the distributed full pass ran once per calibration: its tags
            # are the per-layer halo volume of full-graph inference
            snap["full_pass_halo_bytes"] = {
                t: b for t, b in sess.halo_stats.bytes_by_tag.items()
                if t.startswith("layer")}
            snap["full_pass_latency"] = _executor_compare(
                store, fam, p, spmd_ok, pass_repeats)
            snap["bn_calibration_drift"] = _bn_drift(
                store, fam, p, "spmd" if spmd_ok else "host")
            # the gcn P=2 pipelined run also exports a Chrome trace of every
            # batch's span tree (the CI workflow uploads it as an artifact)
            trace_path = (RESULTS / "TRACE_sharded_serve.json"
                          if fam == "gcn" and p == 2 else None)
            if trace_path is not None:
                RESULTS.mkdir(parents=True, exist_ok=True)
            snap["pipeline"] = _pipeline_compare(store, fam, p, executor,
                                                 nodes, batch,
                                                 trace_path=trace_path)
            fam_out[f"P{p}"] = snap
            pipe = snap["pipeline"]
            csv_row(f"sharded_serve/{fam}/P{p}/pipeline",
                    1e6 / max(pipe["qps_pipelined"], 1e-9),
                    f"qps={pipe['qps_pipelined']:.1f};"
                    f"overlap={pipe['overlap_ratio']:.2f};"
                    f"serve_x_fifo={pipe['serve_x_bytes_fifo']};"
                    f"serve_x_halo_aware="
                    f"{pipe['serve_x_bytes_halo_aware']};"
                    f"halo_saved={pipe['halo_bytes_saved_measured']};"
                    f"steady_compiles={pipe['steady_state_compiles']}")
            halo = ";".join(f"{t.replace('/', '_')}={b}"
                            for t, b in
                            sorted(snap["full_pass_halo_bytes"].items()))
            lat = snap["full_pass_latency"]
            spmd_s = lat["spmd_full_pass_s"]
            drift = snap["bn_calibration_drift"]
            csv_row(f"sharded_serve/{fam}/P{p}",
                    1e6 / max(snap["qps"], 1e-9),
                    f"qps={snap['qps']:.1f};"
                    f"p50_ms={snap['latency']['p50_ms']:.2f};"
                    f"p99_ms={snap['latency']['p99_ms']:.2f};"
                    f"halo_bytes={snap['halo_bytes']};{halo};"
                    f"steady_compiles={snap['steady_state_compiles']};"
                    f"host_pass_ms={lat['host_full_pass_s']*1e3:.2f};"
                    f"spmd_pass_ms="
                    f"{'n/a' if spmd_s is None else f'{spmd_s*1e3:.2f}'};"
                    f"spmd_bit_exact={lat['spmd_bit_exact']};"
                    f"bn_drift_max={drift['max_abs_logit_delta']:.2e};"
                    f"bn_argmax_agree={drift['argmax_agreement']:.4f}")
        summary["families"][fam] = fam_out

    summary["tenants"] = _bench_tenants_sharded(
        store, "gcn", SHARD_COUNTS[0], executor, d.n_nodes, batch,
        n_queries)
    ten = summary["tenants"]
    csv_row("sharded_serve/tenants", 0.0,
            f"gold_qps={ten['tenants']['gold']['qps']:.1f};"
            f"base_qps={ten['tenants']['base']['qps']:.1f};"
            f"mixed_batches={ten['tenant_mixed_batches']}")

    # multi-bucket co-launch per shard count + the fused sharded path's
    # bitwise identity with the unfused single-host forward
    summary["kernels"] = {
        f"P{p}": _multi_bucket_compare(store, "gcn", p, executor, nodes,
                                       batch)
        for p in SHARD_COUNTS}
    summary["kernels"]["fused_sharded_bit_exact"] = _fused_sharded_bit_exact(
        d, "gcn", SHARD_COUNTS[0], batch, hidden)
    for p in SHARD_COUNTS:
        mb = summary["kernels"][f"P{p}"]
        csv_row(f"sharded_serve/kernels/P{p}/multi_bucket", 0.0,
                f"batches={mb['n_batches_multi']};"
                f"serial_dispatches={mb['serial_dispatches']};"
                f"coalesced_dispatches={mb['coalesced_dispatches']};"
                f"dispatch_reduction={mb['dispatch_reduction']:.2f}x;"
                f"replay_bit_exact={mb['replay_bit_exact']}")
    csv_row("sharded_serve/kernels/fused", 0.0,
            f"fused_sharded_bit_exact="
            f"{summary['kernels']['fused_sharded_bit_exact']}")

    RESULTS.mkdir(parents=True, exist_ok=True)
    out = RESULTS / "BENCH_sharded_serve.json"
    out.write_text(json.dumps(summary, indent=2))
    csv_row("sharded_serve/summary", 0.0, f"wrote={out}")
    return summary


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--executor", choices=("host", "spmd"), default="host",
                    help="executor the sharded ENGINE benches run with; "
                    "the host-vs-SPMD full-pass comparison always emits")
    ap.add_argument("--pipeline", action="store_true",
                    help="run the engine benches with the double-buffered "
                    "extract/compute pipeline (depth "
                    f"{PIPELINE_DEPTH}); the pipelined-vs-FIFO comparison "
                    "section always emits")
    args = ap.parse_args()
    run(full=args.full, executor=args.executor, pipeline=args.pipeline)
