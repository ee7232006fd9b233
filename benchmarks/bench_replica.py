"""Replica-tier benchmark: availability and latency of the fault-tolerant
front door under steady state, injected chaos, and a live reshard.

Three sections, all emitted every run (``--chaos`` additionally ENFORCES
the chaos/reshard bounds in-process and exits non-zero on violation — the
CI smoke mode):

  * ``steady``  — P=2 replicas x 2 shards serving a clean wave: tier QPS,
    end-to-end p50/p99, availability (answered / accepted), zero dropped
    queries, zero steady-state recompiles.
  * ``chaos``   — the same tier with one replica killed mid-wave: every
    accepted query must complete on the survivor (availability 1.0,
    ``dropped_queries`` 0), the survivors' batch logs must replay bit-exact
    against the single-host session, and the survivor must take zero
    steady-state recompiles through the failover.
  * ``reshard`` — one replica live-resharded P=2 -> P=4 under load:
    ``blip_p99_ms`` (end-to-end p99 of the queries in flight across the
    swap window) vs the steady p99, the bound ``blip_p99_ms <
    max(5 x steady p99, 1s)``, and zero dropped queries.

Leaves feed ``compare_bench.py``: ``availability`` is higher-is-better,
``dropped_queries`` is zero-tolerance, ``p50_ms``/``p99_ms``/``qps`` use
the standard bands. Emits ``results/BENCH_replica.json``.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.graphs.datasets import make_dataset
from repro.models import gnn
from repro.serve import (FaultInjector, FrontDoor, GraphStore,
                         HealthPolicy, Resharder, SpanTracer,
                         build_replica)

from .common import csv_row

RESULTS = Path(__file__).resolve().parents[1] / "results"

# bump when the emitted JSON layout changes
SCHEMA_VERSION = 1

BATCH = 8
HIDDEN = 16
BLIP_RATIO_BOUND = 5.0       # reshard p99 blip < 5x steady p99 ...
BLIP_FLOOR_S = 1.0           # ... with a smoke-scale noise floor


def _tier(data, params, n_replicas=2, n_shards=2, deadline_s=0.05):
    faults = FaultInjector(seed=0)
    tracer = SpanTracer()
    models = {"gcn": ("gcn", params)}
    reps = [build_replica(f"r{i}", data, models, n_shards=n_shards,
                          faults=faults, tracer=tracer, max_batch=BATCH,
                          mode="subgraph", retry_backoff_s=0.001)
            for i in range(n_replicas)]
    fd = FrontDoor(reps, faults=faults, tracer=tracer, spread="query",
                   policy=HealthPolicy(deadline_s=deadline_s))
    for r in reps:
        r.engine.warmup("g", "gcn")
    return fd, reps, faults


def _single_session(data, params):
    st = GraphStore(max_batch=BATCH)
    st.register_graph("g", data)
    st.register_model("gcn", "gcn", params)
    return st.session("g", "gcn")


def _replay_bit_exact(engine, single) -> bool:
    for batch in engine.batch_log:
        seeds = np.asarray([q.node for q in batch], np.int64)
        want = np.asarray(single.serve_subgraph(seeds))
        for i, q in enumerate(batch):
            if not np.array_equal(np.asarray(q.logits), want[i]):
                return False
    return True


def _wave_stats(fd, qs) -> dict:
    accepted = [q for q in qs if not q.rejected]
    answered = [q for q in accepted if q.done]
    dropped = len(accepted) - len(answered)
    lat = np.asarray([q.latency_s for q in answered]) * 1e3 \
        if answered else np.asarray([0.0])
    m = fd.metrics
    return dict(
        accepted=len(accepted), answered=len(answered),
        dropped_queries=dropped,
        availability=len(answered) / max(len(accepted), 1),
        qps=m.qps, p50_ms=float(np.percentile(lat, 50)),
        p99_ms=float(np.percentile(lat, 99)))


def _bench_steady(data, params, n_queries: int) -> dict:
    fd, reps, _ = _tier(data, params)
    c0 = sum(r.engine.compile_count for r in reps)
    rng = np.random.default_rng(0)
    qs = fd.submit_many("g", "gcn",
                        rng.integers(0, data.n_nodes, size=n_queries))
    fd.run_until_drained(max_ticks=200_000)
    out = _wave_stats(fd, qs)
    out["steady_state_compiles"] = \
        sum(r.engine.compile_count for r in reps) - c0
    for r in reps:
        r.engine.close()
    return out


def _bench_chaos(data, params, n_queries: int, single) -> dict:
    fd, reps, faults = _tier(data, params, deadline_s=0.05)
    survivor = reps[0].engine
    rng = np.random.default_rng(1)
    qs = fd.submit_many("g", "gcn",
                        rng.integers(0, data.n_nodes, size=n_queries))
    for _ in range(3):
        fd.tick()
    c0 = survivor.compile_count
    faults.kill("r1")
    time.sleep(0.06)
    fd.run_until_drained(max_ticks=200_000)
    out = _wave_stats(fd, qs)
    out["failovers"] = fd.failovers
    out["failover_queries"] = fd.failover_queries
    out["replay_bit_exact"] = all(
        _replay_bit_exact(r.engine, single) for r in reps)
    out["steady_state_compiles"] = survivor.compile_count - c0
    for r in reps:
        r.engine.close()
    return out


def _bench_reshard(data, params, n_queries: int, single) -> dict:
    fd, reps, _ = _tier(data, params, n_replicas=1, deadline_s=10.0)
    handle = reps[0]
    rng = np.random.default_rng(2)
    # steady window on P=2 first: the blip baseline
    warm = fd.submit_many("g", "gcn",
                          rng.integers(0, data.n_nodes, size=n_queries))
    fd.run_until_drained(max_ticks=200_000)
    steady = _wave_stats(fd, warm)
    # queries in flight ACROSS the swap window feel the blip
    blip_qs = fd.submit_many("g", "gcn",
                             rng.integers(0, data.n_nodes,
                                          size=n_queries // 2))
    for _ in range(2):
        fd.tick()
    rs = Resharder(handle, "g", "gcn", 4, drain_timeout_s=60.0,
                   tracer=fd.tracer)
    rs.prepare(block=False)      # P' builds in the background ...
    while not rs.ready:
        fd.tick()                # ... while the old engine keeps serving
    report = rs.swap()
    post = fd.submit_many("g", "gcn",
                          rng.integers(0, data.n_nodes,
                                       size=n_queries // 2))
    fd.run_until_drained(max_ticks=200_000)
    answered = [q for q in blip_qs + post if q.done]
    accepted = [q for q in blip_qs + post if not q.rejected]
    lat = np.asarray([q.latency_s for q in answered]) * 1e3 \
        if answered else np.asarray([0.0])
    blip_p99 = float(np.percentile(lat, 99))
    out = dict(
        steady_p50_ms=steady["p50_ms"], steady_p99_ms=steady["p99_ms"],
        blip_p99_ms=blip_p99,
        blip_ratio=blip_p99 / max(steady["p99_ms"], 1e-9),
        blip_bound_ms=max(BLIP_RATIO_BOUND * steady["p99_ms"],
                          BLIP_FLOOR_S * 1e3),
        dropped_queries=(len(accepted) - len(answered)
                         + report.drain.shed),
        availability=len(answered) / max(len(accepted), 1),
        from_shards=report.from_shards, to_shards=report.to_shards,
        prepare_s=report.prepare_s, swap_s=report.swap_s,
        drain=report.drain.to_json(),
        replay_bit_exact=_replay_bit_exact(handle.engine, single))
    out["blip_bounded"] = out["blip_p99_ms"] < out["blip_bound_ms"]
    handle.engine.close()
    return out


def run(full: bool = False, chaos: bool = False) -> dict:
    scale = 0.3 if full else 0.05
    n_queries = 256 if full else 48
    data = make_dataset("cora", seed=0, scale=scale)
    params = gnn.init_gcn(jax.random.PRNGKey(0), data.x.shape[1], HIDDEN,
                          data.n_classes)
    single = _single_session(data, params)

    summary = dict(schema_version=SCHEMA_VERSION,
                   config=dict(full=full, n_queries=n_queries,
                               scale=scale))
    summary["steady"] = _bench_steady(data, params, n_queries)
    s = summary["steady"]
    csv_row("replica/steady", 1e6 / max(s["qps"], 1e-9),
            f"qps={s['qps']:.1f};p50_ms={s['p50_ms']:.2f};"
            f"p99_ms={s['p99_ms']:.2f};availability={s['availability']};"
            f"dropped={s['dropped_queries']};"
            f"steady_compiles={s['steady_state_compiles']}")

    summary["chaos"] = _bench_chaos(data, params, n_queries, single)
    c = summary["chaos"]
    csv_row("replica/chaos", 0.0,
            f"availability={c['availability']};"
            f"dropped={c['dropped_queries']};failovers={c['failovers']};"
            f"moved={c['failover_queries']};"
            f"replay_bit_exact={c['replay_bit_exact']};"
            f"survivor_steady_compiles={c['steady_state_compiles']}")

    summary["reshard"] = _bench_reshard(data, params, n_queries, single)
    r = summary["reshard"]
    csv_row("replica/reshard", 0.0,
            f"blip_p99_ms={r['blip_p99_ms']:.2f};"
            f"steady_p99_ms={r['steady_p99_ms']:.2f};"
            f"blip_bounded={r['blip_bounded']};"
            f"dropped={r['dropped_queries']};"
            f"prepare_s={r['prepare_s']:.2f};swap_s={r['swap_s']:.2f};"
            f"replay_bit_exact={r['replay_bit_exact']}")

    RESULTS.mkdir(parents=True, exist_ok=True)
    out = RESULTS / "BENCH_replica.json"
    out.write_text(json.dumps(summary, indent=2))
    csv_row("replica/summary", 0.0, f"wrote={out}")

    if chaos:
        # CI smoke mode: the availability/bit-exactness/blip bounds are
        # hard failures here, independent of the compare_bench gate
        problems = []
        if c["availability"] < 1.0 or c["dropped_queries"]:
            problems.append(f"chaos lost queries: {c}")
        if not c["replay_bit_exact"]:
            problems.append("chaos replay not bit-exact")
        if c["steady_state_compiles"]:
            problems.append(
                f"survivor recompiled {c['steady_state_compiles']}x")
        if r["dropped_queries"]:
            problems.append(f"reshard dropped {r['dropped_queries']}")
        if not r["blip_bounded"]:
            problems.append(
                f"reshard blip {r['blip_p99_ms']:.1f}ms over bound "
                f"{r['blip_bound_ms']:.1f}ms")
        if not r["replay_bit_exact"]:
            problems.append("reshard replay not bit-exact")
        for p in problems:
            print(f"CHAOS-FAIL {p}")
        if problems:
            raise SystemExit(1)
    return summary


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--chaos", action="store_true",
                    help="enforce the chaos/reshard availability + blip "
                         "bounds (exit 1 on violation) — the CI smoke")
    args = ap.parse_args()
    run(full=args.full, chaos=args.chaos)
