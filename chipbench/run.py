"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout. The cell's configuration, traffic mix and
rates are data files under ``chipbench/`` found by the names in
``BENCHMARK.json`` (see ``chipbench/README.md``). Set-up builds the graph
from the configuration, the weights and all traffic from ``--seed``,
compiles and warms every shape the window uses, then the open-loop driver
offers the traffic for ``--seconds``. After the window the answers are
compared with the plain reference. The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` a ``breakdown``), then ``checks``, each
number compared beside its limit; the same checks end standard error.

It refuses to run (exit code 2, no result line) unless JAX's devices are
TPUs, as many as the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from chipbench import correctness, spec, traffic, xplane  # noqa: E402
from chipbench.record import FULL_FORWARD, SERVE_FORWARD, Run  # noqa: E402

# JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
GRAPH, MODEL = "graph", "model"
DRAIN_LIMIT_S = 60.0
WARM_REFRESHES = 3


class NoAccelerator(RuntimeError):
    pass


def log(*parts) -> None:
    print(*parts, flush=True)


def configure_jax() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_devices(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devs[0].platform!r}); not running")
    if len(devs) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs


def prng_key(seed: int):
    """A JAX key from a seed of any size (PRNGKey keeps 32 bits)."""
    import jax
    key = jax.random.PRNGKey(seed % 2 ** 32)
    return jax.random.fold_in(key, seed // 2 ** 32)


def make_params(family: str, cfg: dict, seed: int):
    """The float parameters, made on the device in one jitted call."""
    import jax
    from repro.models import gnn
    init = getattr(gnn, f"init_{family}")
    fn = jax.jit(lambda k: init(k, cfg["n_features"], cfg["hidden"],
                                cfg["n_classes"]))
    return jax.block_until_ready(fn(prng_key(seed)))


def nearest_rank(values: np.ndarray, q: float) -> float:
    """The q-quantile (0 < q < 1) by nearest rank; inf counts as missing."""
    if values.size == 0:
        return float("nan")
    v = np.sort(values)
    return float(v[max(int(math.ceil(q * v.size)) - 1, 0)])


class GCPauses:
    """The interpreter's garbage collections in the window: a callback of
    ``gc.callbacks`` times each one, so a stall can be told apart from
    the engine's own work on the earlier lines."""

    def __init__(self):
        self.start: Optional[float] = None
        self.pauses: List[tuple] = []        # (generation, seconds)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.start = time.perf_counter()
        elif self.start is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self.start))
            self.start = None

    def summary(self) -> dict:
        out = {}
        for g in sorted({g for g, _ in self.pauses}):
            t = [s for gen, s in self.pauses if gen == g]
            out[f"gen{g}"] = dict(n=len(t), total_ms=1e3 * sum(t),
                                  max_ms=1e3 * max(t))
        return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, overrides: Optional[dict] = None,
             patch: Optional[Callable] = None, control: bool = False
             ) -> dict:
    """One run of one cell. Returns the result object (with ``checks``)
    plus, under ``"_info"``, what the earlier output lines print.

    ``overrides`` replaces keys of the cell, configuration or mix
    (``{"cell": {...}, "config": {...}, "traffic": {...}}``), for runs at a
    small size off the chip; ``patch(engine, session, store)`` is called
    after warm-up, to break the timed path in a test; ``control`` also
    computes the numbers of the lower-precision control."""
    import jax
    from repro.graphs.datasets import make_dataset
    from repro.serve import GNNServeEngine, GraphStore

    overrides = overrides or {}
    bench = spec.benchmark()
    wl = spec.workload(bench, workload)
    cell = {**spec.cell(workload), **overrides.get("cell", {})}
    cfg = {**spec.config(cell["config"]), **overrides.get("config", {})}
    mix = {**spec.traffic(cell["traffic"]), **overrides.get("traffic", {})}
    family = cfg["family"]
    info: Dict[str, object] = {}

    devs = check_devices(wl["chips"]) if require_tpu else jax.devices()
    dev = devs[0]
    peaks = spec.peaks(dev.device_kind) if require_tpu else None

    # ---------------------------------------------------------- set-up --
    phases = {"start_s": time.perf_counter() - T_PROCESS}
    ds = cfg["dataset"]
    data = make_dataset(ds["name"], seed=ds["seed"], scale=ds["scale"])
    phases["dataset_s"] = time.perf_counter() - T_PROCESS
    n = data.n_nodes
    params = make_params(family, cfg, seed)
    rng = np.random.default_rng(seed)
    pop = traffic.Popularity(mix["popularity"], n)
    upd = mix.get("updates")
    versions = ([data.x] if not upd else
                traffic.feature_versions(data.x, upd, pop, rng))
    st = cfg["store"]
    store = GraphStore(max_batch=st["max_batch"], khop=st["khop"],
                       use_pallas=st["use_pallas"],
                       incremental=st["incremental"])
    store.register_graph(GRAPH, data)
    store.register_model(MODEL, family, params)
    session = store.session(GRAPH, MODEL)
    phases["session_s"] = time.perf_counter() - T_PROCESS
    eng = cfg["engine"]
    engine = GNNServeEngine(store, max_batch=eng["max_batch"],
                            mode=mix["mode"],
                            pipeline_depth=eng["pipeline_depth"])
    full_cache = mix["mode"] == "full" or (
        mix["mode"] == "auto" and n <= engine.full_cache_max_nodes)
    info["plan"] = dict(
        plan=session.plan.name(), family=family, nodes=n,
        edges=int(data.n_edges), full_cache=full_cache,
        mode=mix["mode"], max_batch=engine.max_batch, khop=session.khop,
        use_pallas=session.use_pallas, pipeline_depth=engine.pipeline_depth,
        incremental=session.incremental,
        query_rate_per_s=cell["query_rate_per_s"],
        update_rate_per_s=cell.get("update_rate_per_s"))
    version_of: Dict[int, int] = {store.graphs[GRAPH].version: 0}

    def apply_update(idx: int) -> None:
        store.update_features(GRAPH, versions[idx])
        version_of[store.graphs[GRAPH].version] = idx

    # the engine's own warm-up, as a deployment calls it: on the subgraph
    # path it probes 16 batches to set the shape buckets every later
    # batch pads to, and compiles that shape
    engine.warmup(GRAPH, MODEL)
    if full_cache:
        # refresh path warm: upload, calibrated forward and copy-back
        for k in range(WARM_REFRESHES):
            apply_update((k + 1) % len(versions) if upd else 0)
            engine.submit(GRAPH, MODEL, int(pop.draw(rng, 1)[0]))
            engine.tick()
        if upd:
            apply_update(0)
        engine.submit(GRAPH, MODEL, int(pop.draw(rng, 1)[0]))
        engine.tick()
    engine.batch_log.clear()
    phases["warm_s"] = time.perf_counter() - T_PROCESS
    info["setup_phases"] = phases
    sched = traffic.make_schedule(mix, cell, pop, rng, seconds)
    if patch is not None:
        patch(engine, session, store)
    compiles0 = engine.compile_count
    gc.collect()
    gc_pauses = GCPauses()
    gc.callbacks.append(gc_pauses)

    # ---------------------------------------------------------- window --
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    m0 = dict(extract_s=engine.metrics.extract_s,
              compute_s=engine.metrics.compute_s,
              batches=engine.metrics.batches)
    loop = drive(engine, session, store, sched, seconds, apply_update)
    gc.callbacks.remove(gc_pauses)
    if trace:
        jax.profiler.stop_trace()
    info["gc"] = gc_pauses.summary()
    stages = dict(extract_s=engine.metrics.extract_s - m0["extract_s"],
                  compute_s=engine.metrics.compute_s - m0["compute_s"],
                  batches=engine.metrics.batches - m0["batches"])
    window_compiles = engine.compile_count - compiles0

    # drain: a cell below capacity owes every query due in the window
    drained_s = 0.0
    if upd:
        t_d = time.perf_counter()
        while loop["outstanding"] and \
                time.perf_counter() - t_d < DRAIN_LIMIT_S:
            k = engine.tick()
            loop["outstanding"] -= k
            if k:
                loop["served"].append((list(engine.batch_log[-1]),
                                       session.feature_version,
                                       time.perf_counter()))
        drained_s = time.perf_counter() - t_d

    memory_peak = max(int(d.memory_stats().get("peak_bytes_in_use", 0))
                      for d in devs) if require_tpu else 0

    # --------------------------------------------------------- metrics --
    queries = loop["queries"]
    due = loop["due"]
    done = np.array([q.t_done if q.done else np.inf for q in queries])
    lat = (done - due) * 1e3
    attempted = len(queries)
    answered_in_window = int(np.sum(done <= loop["t1"]))
    failed_q = int(sum(1 for q in queries if q.failed or q.rejected))
    metrics: Dict[str, dict] = {}
    e2e: Dict[str, float] = {
        "setup_s": loop["t0"] - T_PROCESS,
        "qps": answered_in_window / loop["window_s"],
        "p95_ms": nearest_rank(lat, 0.95),
        "latency_mean_ms": float(np.mean(lat)) if lat.size else np.inf,
    }
    stale = staleness(loop)
    e2e["stale_p90_ms"] = nearest_rank(stale, 0.90) if stale.size else \
        float("nan")
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    info["lateness_ms"] = lateness_summary(loop)
    info["counts"] = dict(
        attempted=attempted, answered_in_window=answered_in_window,
        answered=int(np.isfinite(done).sum()), failed=failed_q,
        updates=len(loop["updates"]), window_s=loop["window_s"],
        drain_s=drained_s, ticks=len(loop["ticks"]),
        compiles_in_window=window_compiles,
        latency_ms=dict(
            p50=nearest_rank(lat, 0.5), p90=nearest_rank(lat, 0.9),
            p95=nearest_rank(lat, 0.95), p99=nearest_rank(lat, 0.99),
            mean=e2e["latency_mean_ms"],
            max=float(np.max(lat)) if lat.size else float("nan")),
        stale_ms=dict(n=int(stale.size),
                      p50=nearest_rank(stale, 0.5),
                      p90=nearest_rank(stale, 0.9),
                      max=float(np.max(stale)) if stale.size else None))

    ticks = np.array(loop["ticks"]) if loop["ticks"] else \
        np.zeros((0, 4))
    tick_ms = (ticks[:, 1] - ticks[:, 0]) * 1e3
    refreshed = ticks[:, 3] > 0
    info["ticks_ms"] = dict(refreshes=int(refreshed.sum()))
    for name, sel in (("refresh", refreshed), ("other", ~refreshed)):
        if sel.any():
            info["ticks_ms"].update({
                f"{name}_mean": float(tick_ms[sel].mean()),
                f"{name}_p99": nearest_rank(tick_ms[sel], 0.99),
                f"{name}_max": float(tick_ms[sel].max())})
    breakdown = None
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(devs), memory_peak_bytes=memory_peak)
    if trace:
        files = sorted(Path(tdir).rglob("*.xplane.pb"))
        tr = xplane.load(files[0]) if files else None
        shutil.rmtree(tdir, ignore_errors=True)
        work_mod = spec.family_module("work", family)
        rec = Run(
            tick_start=ticks[:, 0], tick_end=ticks[:, 1],
            tick_answered=ticks[:, 2], tick_invalidated=ticks[:, 3] > 0,
            batches=[np.array([q.node for q in b], np.int64)
                     for b, _, _ in loop["served_window"]],
            stages=stages, n_nodes=n,
            indptr=store.graphs[GRAPH].csr.indptr,
            indices=store.graphs[GRAPH].csr.indices,
            work=lambda rows, nnz: work_mod.forward(
                rows, nnz, cfg["n_features"], cfg["hidden"],
                cfg["n_classes"]),
            peaks=peaks, full_cache=full_cache, trace=tr)
        breakdown = read_trace(rec, spec.per_layer(bench, workload),
                               metrics, device, info)
    else:
        for m in spec.end_to_end(bench, workload):
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": units[m["name"]]}

    # ----------------------------------------------------- correctness --
    answers, missing = owed_answers(loop, version_of, upd is not None)
    ref_mod = spec.family_module("reference", family)
    needed = sorted({v for _, v, _ in answers}) or [0]
    xs = {k: versions[k] for k in needed}
    host_params = [np.asarray(p) for p in params]
    del engine, session, store, params
    gc.collect()
    refs = correctness.reference_logits(ref_mod.forward, host_params, xs,
                                        data.edges)
    frag = correctness.reference_fragile(ref_mod, host_params, xs,
                                         data.edges)
    served, ref, fragile = correctness.gather(answers, refs, frag)
    unshown = int(np.sum(~np.isfinite(stale)))
    nums = correctness.numbers(served, ref, missing, unshown, fragile)
    ok, checks = correctness.judge(nums, cell["limits"])
    info["compared"] = nums
    if control:
        low = correctness.reference_logits(
            ref_mod.forward, host_params, xs, data.edges, dtype="bfloat16")
        c_served = np.stack([low[v][node] for node, v, _ in answers]) \
            if answers else served
        info["control"] = correctness.numbers(c_served, ref, missing,
                                              unshown, fragile)
        # the control in the program's place, judged as a run is
        info["control_correct"], _ = correctness.judge(info["control"],
                                                       cell["limits"])

    result = {"correct": ok, "attempted": attempted, "failed": missing,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    result["_info"] = info
    return result


def read_trace(rec: Run, layer_metrics: List[dict], metrics: dict,
               device: dict, info: dict) -> Optional[dict]:
    """Per-layer metrics by their readers, the device's busy time and the
    breakdown, from a traced run; returns the breakdown."""
    for m in layer_metrics:
        v = spec.reader(m["name"]).read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    tr = rec.trace
    if tr is None:
        return None
    lo, hi = xplane.window(tr)
    device["busy_s"] = xplane.busy_seconds(tr, lo, hi)
    device["window_s"] = (hi - lo) * 1e-9
    plane = sorted(tr.ops)[0] if tr.ops else ""
    idle = xplane.gaps(xplane.busy_intervals(tr, plane, lo, hi), lo, hi)
    labelled = sorted(xplane.label_gaps(idle, tr.spans).items(),
                      key=lambda kv: -kv[1])[:10]
    info["programs"] = {
        "full_forward": len(rec.program_seconds(FULL_FORWARD)),
        "serve_forward": len(rec.program_seconds(SERVE_FORWARD))}
    return {"device_ops": xplane.top_ops(tr, lo, hi),
            "idle_gaps": [[k, v] for k, v in labelled]}


def drive(engine, session, store, sched, seconds: float,
          apply_update: Callable[[int], None]) -> dict:
    """The open loop. One thread: each turn submits every query that is
    due, applies a feature update if one is due, and ticks the engine.
    Every call into the engine is a profiler span of its own."""
    from jax.profiler import TraceAnnotation

    qt, qn = sched.query_t, sched.query_node
    ut, uv = sched.update_t, sched.update_version
    nq, nu = qt.size, ut.size
    queries: List = []
    sub_t: List[float] = []
    ticks: List[tuple] = []
    served: List[tuple] = []
    updates: List[tuple] = []
    qi = ui = 0
    outstanding = 0
    clock = time.perf_counter
    submit = engine.submit
    with TraceAnnotation("bench.window"):
        t0 = clock()
        t_stop = t0 + seconds
        while True:
            now = clock()
            if now >= t_stop:
                break
            rel = now - t0
            while qi < nq and qt[qi] <= rel:
                with TraceAnnotation("bench.submit"):
                    q = submit(GRAPH, MODEL, int(qn[qi]))
                sub_t.append(clock())
                queries.append(q)
                outstanding += 1
                qi += 1
            if ui < nu and ut[ui] <= rel:
                with TraceAnnotation("bench.update"):
                    apply_update(int(uv[ui]))
                updates.append((ut[ui] + t0, clock(),
                                store.graphs[GRAPH].version))
                ui += 1
            if outstanding:
                inv0 = session.invalidations
                with TraceAnnotation("bench.tick"):
                    ta = clock()
                    k = engine.tick()
                    tb = clock()
                outstanding -= k
                ticks.append((ta, tb, k, session.invalidations - inv0))
                if k:
                    served.append((list(engine.batch_log[-1]),
                                   session.feature_version, tb))
            else:
                nxt = min(qt[qi] if qi < nq else math.inf,
                          ut[ui] if ui < nu else math.inf) + t0
                pause = min(nxt, t_stop) - clock()
                if pause > 2e-4:
                    time.sleep(pause - 1e-4)
        t1 = clock()
    return dict(t0=t0, t1=t1, window_s=t1 - t0, queries=queries,
                due=qt[:qi] + t0, submitted=np.array(sub_t),
                ticks=ticks, served=served, served_window=list(served),
                updates=updates, outstanding=outstanding)


def staleness(loop: dict) -> np.ndarray:
    """ms from each update's return to the first answer at its version or
    a later one (inf where none came)."""
    if not loop["updates"]:
        return np.zeros(0)
    ans_v = np.array([v for _, v, _ in loop["served"]])
    ans_t = np.array([t for _, _, t in loop["served"]])
    out = []
    for _, t_ret, ver in loop["updates"]:
        ok = (ans_v >= ver) & (ans_t >= t_ret)
        out.append((ans_t[ok].min() - t_ret) * 1e3 if ok.any() else np.inf)
    return np.array(out)


def lateness_summary(loop: dict) -> dict:
    late = (loop["submitted"] - loop["due"]) * 1e3
    if late.size == 0:
        return {}
    return dict(n=int(late.size), p50=float(np.median(late)),
                p99=nearest_rank(late, 0.99), max=float(late.max()))


def owed_answers(loop: dict, version_of: Dict[int, int], all_due: bool):
    """(node, version index, logits) of every answer given, and the count
    of answers owed that never came."""
    answers = []
    missing = 0
    for batch, ver, _ in loop["served"]:
        for q in batch:
            if q.done:
                answers.append((q.node, version_of[ver], q.logits))
            else:
                missing += 1
    if all_due:
        missing = sum(1 for q in loop["queries"] if not q.done)
    missing += sum(1 for q in loop["queries"] if q.failed or q.rejected)
    return answers, missing


def print_result(res: dict) -> None:
    info = res.pop("_info")
    for key in ("plan", "setup_phases", "lateness_ms", "counts", "gc",
                "ticks_ms", "programs", "compared", "control",
                "control_correct"):
        if key in info:
            log(f"{key}: {json.dumps(info[key], default=float)}")
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(f"correct: {res['correct']}", file=sys.stderr, flush=True)
    log(json.dumps(finite(res), allow_nan=False))


def finite(obj):
    """``obj`` with every non-finite number (a tail over missing answers,
    an error over no answers) written as null, so the line stays JSON."""
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [finite(v) for v in obj]
    if isinstance(obj, (float, np.floating)):
        return float(obj) if math.isfinite(obj) else None
    if isinstance(obj, np.integer):
        return int(obj)
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    configure_jax()
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print_result(res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
