"""Chip smoke test: the serving system end to end on a TPU.

    python chip_smoke.py               # one chip: phases A, B, C
    python chip_smoke.py --four-chips  # four chips: sharded serving only

One chip drives GNN node-query serving (``GraphStore`` ->
``CompiledGraphSession`` -> ``GNNServeEngine``) on the stat-matched Flickr
graph (89,250 nodes, 500 features, 7 classes) with a random-init binary GCN
of hidden width 64:

  A. the jnp serving forward: ~512 seeded queries on the subgraph path, then
     on the full-cache path — every query answered, zero steady-state
     recompiles, predictions equal to the jitted direct forward on the chip,
     and >= 99% argmax agreement with the same forward on the CPU backend;
  B. the same with the Pallas BSpMM kernels (``use_pallas=True``): the serve
     executable holds ``tpu_custom_call``, predictions equal phase A's, and
     both kernels agree with the jnp aggregation at F = 64 and 500;
  C. the token tier: ``TokenServeEngine`` answers 4 requests per family of
     ``benchmarks/bench_serve_llm.py``'s config at max_batch 1 and 4, equal
     to a jit(decode_step) loop up to a bf16 near-tie.

``--four-chips`` runs only ``ShardedServeEngine`` at P=4 with the SPMD
layer executor over a 4-TPU mesh: ``full_logits()`` bit-exact against the
host executor, served batches bit-exact against the single-host session.

Timings printed on the way are informal, not metrics. The last stdout line
is ``{"ok": true, "device": {...}}``; a missing TPU or any failed check
exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

GRAPH, MODEL = "flickr", "gcn"
SCALE = 1.0            # make_dataset scale: 1.0 = the Table-2 Flickr stats
HIDDEN = 64
BATCH = 32
N_QUERIES = 512
N_SHARDS = 4
TOKEN_REQUESTS = 4
PLATFORM = "tpu"
MIN_CPU_AGREEMENT = 0.99
# phase B vs phase A logits: the Pallas fp aggregation runs the same mask
# matmuls at HIGHEST precision in a different summation order
PALLAS_RTOL, PALLAS_ATOL = 1e-4, 1e-4


FAILURES: list = []


def check(ok: bool, what: str) -> bool:
    """Record one check; a failure does not stop the run, so one call on
    the chip reports every phase."""
    print(f"  {'ok' if ok else 'FAIL'}: {what}", flush=True)
    if not ok:
        FAILURES.append(what)
    return ok


def run_phase(name: str, fn, *args):
    """Run one phase; an exception fails the phase, not the whole run."""
    print(f"phase {name}", flush=True)
    try:
        return fn(*args)
    except Exception:
        traceback.print_exc()
        check(False, f"phase {name} raised")
        return None


def require_tpu(n: int):
    """JAX's devices, or exit unless they hold ``n`` TPUs: never fall back
    to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != PLATFORM or len(devs) < n:
        sys.exit(f"chip_smoke: needs {n} TPU device(s), JAX found "
                 f"{len(devs)} x {devs[0].platform}; not running on that")
    return devs


def same_predictions(want_logits, got_pred, what: str) -> None:
    """Served predictions must equal ``argmax(want_logits)`` exactly."""
    import numpy as np
    want_pred = np.argmax(want_logits, axis=-1)
    differ = int(np.sum(want_pred != got_pred))
    check(differ == 0, f"{what}: predictions equal ({differ} of "
          f"{want_pred.size} differ)")


def build():
    """The Flickr graph and a seeded random-init GCN (no training)."""
    import jax
    from repro.graphs.datasets import make_dataset
    from repro.models import gnn
    t0 = time.perf_counter()
    data = make_dataset(GRAPH, seed=0, scale=SCALE)
    params = gnn.init_gcn(jax.random.PRNGKey(0), data.x.shape[1], HIDDEN,
                          data.n_classes)
    print(f"graph {GRAPH}: {data.n_nodes} nodes, {data.n_edges} edges, "
          f"{data.x.shape[1]} features, {data.n_classes} classes "
          f"(built in {time.perf_counter() - t0:.1f}s)", flush=True)
    return data, params


def make_store(data, params, cache_dir: Path, **kw):
    from repro.serve import GraphStore
    store = GraphStore(cache_dir=str(cache_dir), max_batch=BATCH, **kw)
    store.register_graph(GRAPH, data)
    store.register_model(MODEL, "gcn", params)
    return store


def serve(engine, nodes, label: str):
    """Warm up, serve ``nodes``, check every query is answered with zero
    steady-state recompiles; returns (logits, preds)."""
    import numpy as np
    t0 = time.perf_counter()
    warm = engine.warmup(GRAPH, MODEL)
    c0 = engine.compile_count
    t1 = time.perf_counter()
    queries = engine.submit_many(GRAPH, MODEL, nodes)
    engine.run_until_drained()
    wall = time.perf_counter() - t1
    snap = engine.snapshot()
    print(f"  [{label}] warmup {t1 - t0:.1f}s ({warm} compiles); "
          f"{len(queries)} queries in {wall:.2f}s, informal "
          f"{len(queries) / wall:.0f} QPS", flush=True)
    check(all(q.done for q in queries)
          and not any(q.rejected or q.failed for q in queries),
          f"{label}: all {len(queries)} queries answered, none shed, "
          f"throttled or failed")
    steady = engine.compile_count - c0
    check(steady == 0
          and snap["watchdogs"]["recompile"]["steady_recompiles"] == 0,
          f"{label}: 0 steady-state recompiles")
    return (np.stack([q.logits for q in queries]),
            np.asarray([q.pred for q in queries]))


def direct_forward(sess, x, device=None):
    """The jitted direct ``gcn_forward_bitgnn`` over the full graph on
    ``device`` (default: the default device)."""
    import jax
    import numpy as np
    from repro.models import gnn
    adj, adj_bin, q = sess._adj_full["adj"], sess._adj_full["bin"], \
        sess.qparams
    if device is not None:
        put = lambda t: jax.tree.map(   # noqa: E731
            lambda a: jax.device_put(a, device)
            if isinstance(a, jax.Array) else a, t)
        adj, adj_bin, q, x = put(adj), put(adj_bin), put(q), put(x)
    plan = sess.plan
    fn = jax.jit(lambda xx: gnn.gcn_forward_bitgnn(
        q, xx, adj, adj_bin, scheme=plan.scheme,
        trinary_mode=plan.trinary_mode))
    return np.asarray(fn(x))


def single_chip_phase(data, params, nodes, workdir: Path, use_pallas: bool):
    """Phase A (jnp) or B (Pallas): serve on both paths; phase A checks
    against the direct forward. Both phases share one artifact directory,
    so phase B restores phase A's tuned plan. Returns (subgraph logits,
    full-cache logits, session)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serve import GNNServeEngine
    label = "pallas" if use_pallas else "jnp"
    store = make_store(data, params, workdir, use_pallas=use_pallas)
    t0 = time.perf_counter()
    sess = store.session(GRAPH, MODEL, tune=True)
    print(f"  [{label}] session {sess.plan.name()} built in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    sub_logits, sub_pred = serve(
        GNNServeEngine(store, max_batch=BATCH, mode="subgraph"), nodes,
        f"{label}/subgraph")
    full_logits, full_pred = serve(
        GNNServeEngine(store, max_batch=BATCH, mode="full"), nodes,
        f"{label}/full-cache")
    if not use_pallas:
        direct = direct_forward(sess, jnp.asarray(data.x))[nodes]
        scale = float(np.abs(direct).max())
        for logits, pred, path in ((sub_logits, sub_pred, "subgraph"),
                                   (full_logits, full_pred, "full-cache")):
            same_predictions(direct, pred,
                             f"{label}/{path} vs the direct forward")
            check(np.allclose(logits, direct, rtol=1e-5, atol=1e-5 * scale),
                  f"{label}/{path} logits allclose to the direct forward "
                  f"(max |d| {float(np.abs(logits - direct).max()):.3g})")
    return sub_logits, full_logits, sess


def cpu_agreement(sess, data) -> None:
    """The direct forward on the chip vs the same on the CPU backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    chip = direct_forward(sess, jnp.asarray(data.x))
    with jax.default_device(cpu):
        host = direct_forward(sess, jnp.asarray(data.x), device=cpu)
    agree = float(np.mean(np.argmax(chip, -1) == np.argmax(host, -1)))
    delta = float(np.abs(chip - host).max())
    print(f"  [cpu check] argmax agreement {agree:.6f} over {chip.shape[0]} "
          f"nodes, max |dlogit| {delta:.6g} (max |logit| "
          f"{float(np.abs(host).max()):.6g}); {time.perf_counter() - t0:.1f}s",
          flush=True)
    check(agree >= MIN_CPU_AGREEMENT,
          f"argmax agreement with the CPU backend {agree:.4f} >= "
          f"{MIN_CPU_AGREEMENT}")


def hlo_has_kernel(sess, nodes) -> None:
    """The compiled subgraph serve executable runs the Pallas kernels."""
    import jax.numpy as jnp
    prepared = sess.prepare_batch(nodes[:BATCH])
    staged = prepared.groups[0].staged
    text = sess.core._jit_serve.lower(
        jnp.asarray(staged.x_pad), prepared.bn, staged.adjs,
        jnp.asarray(staged.pos_pad)).compile().as_text()
    check("tpu_custom_call" in text,
          "pallas serve executable contains tpu_custom_call")


def kernel_agreement(data) -> None:
    """Both BSpMM kernels against the jnp aggregation on the full Flickr
    adjacencies at the hidden and the input width, whichever variants the
    tuned plan picked: bit counts exactly, fp within PALLAS_RTOL/ATOL."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import bitops
    from repro.core import bspmm as bspmm_core
    from repro.core.binarize import BinTensor
    from repro.kernels import ops
    from repro.serve import session_core
    rng = np.random.default_rng(2)
    for f in (HIDDEN, data.x.shape[1]):
        x = jnp.asarray(rng.standard_normal((data.n_nodes, f)), jnp.float32)
        cases = (("bits", data.adjacency("binary"),
                  bitops.sign_bits(x, axis=-1), "BBF"),
                 ("fp", data.adjacency("gcn"), x, "FBF"))
        for kind, adj, operand, variant in cases:
            def agg(arrs, o, m=adj, v=variant, f=f):
                if v == "BBF":
                    o = BinTensor(packed=o, scale=jnp.ones((o.shape[0], 1)),
                                  n=f)
                return bspmm_core.bspmm(session_core.frdc_rebuild(
                    arrs, m.n_rows, m.n_cols, m.nnz), o, v)
            arrs = session_core.frdc_arrays(adj)
            want = np.asarray(jax.jit(agg)(arrs, operand))
            with ops.serve_kernels():
                got = np.asarray(jax.jit(lambda a, o: agg(a, o))(arrs,
                                                                 operand))
            d = float(np.abs(got - want).max())
            if kind == "bits":
                check(np.array_equal(got, want),
                      f"bspmm_bits F={f}: kernel counts equal jnp (max |d| "
                      f"{d:.3g})")
            else:
                scale = float(np.abs(want).max())
                check(np.allclose(got, want, rtol=PALLAS_RTOL,
                                  atol=PALLAS_ATOL * scale),
                      f"bspmm_fp F={f}: kernel allclose to jnp (max |d| "
                      f"{d:.3g}, max |out| {scale:.3g})")


def decode_loop(cfg, params, prompt, max_new: int):
    """The oracle: a python loop of jit(decode_step) at batch 1 with argmax
    feedback. Returns the generated tokens and, per token, the top-2 logit
    margin and the bf16 rounding step at the top logit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer
    step = jax.jit(lambda p, c, t, pos: transformer.decode_step(
        p, cfg, c, t, pos))
    total = prompt.size + max_new
    cache = transformer.init_cache(
        cfg, 1, max(64, int(2 ** np.ceil(np.log2(total)))))
    out, margins, steps, prev = [], [], [], None
    for t in range(total - 1):
        tok = prompt[t] if t < prompt.size else prev
        lg, cache = step(params, cache, jnp.asarray([[tok]], jnp.int32), t)
        logits = np.asarray(lg[0, 0, :cfg.vocab], np.float32)
        prev = int(np.argmax(logits))
        if t >= prompt.size - 1:
            top2 = np.sort(logits)[-2:]
            out.append(prev)
            margins.append(float(top2[1] - top2[0]))
            # bf16 keeps 8 significant bits
            steps.append(2.0 ** (np.floor(np.log2(abs(top2[1]) or 1.0)) - 7))
    return np.asarray(out, np.int32), margins, steps


def token_phase() -> None:
    """Phase C: the token tier against the jit(decode_step) loop.

    The model computes in bf16, and the served program (a chunked decode,
    batched at max_batch 4, the bench's batch) rounds differently from the
    loop's batch-1 step by a step now and then, at max_batch 1 as well: on
    a v5e one rwkv6 stream of four parted at its first token, where the
    loop's top-2 logits were one bf16 step apart. So each stream must
    equal the loop's, or first differ at a token whose top-2 margin in the
    loop is within one bf16 step, where argmax may go either way; a wrong
    state or cache parts at a clear margin."""
    import jax
    import numpy as np
    from benchmarks.bench_serve_llm import ARCHS
    from repro.configs import get_config, reduced_config
    from repro.models import transformer
    from repro.serve import TokenServeEngine, TokenStore
    max_new = 8
    for kind in sorted(ARCHS):
        cfg = reduced_config(get_config(ARCHS[kind])).resolve_for_mesh(tp=1)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab, int(rng.integers(3, 12)))
                   .astype(np.int32) for _ in range(TOKEN_REQUESTS)]
        refs = [decode_loop(cfg, params, p, max_new) for p in prompts]
        for batch in (1, TOKEN_REQUESTS):
            label = f"token/{kind}/max_batch={batch}"
            store = TokenStore(max_batch=batch, max_len=256, chunk=4,
                               warm_len=12, warm_new=max_new)
            store.register_model("lm", cfg, params)
            engine = TokenServeEngine(store)
            t0 = time.perf_counter()
            engine.warmup("lm")
            c0 = engine.compile_count
            queries = engine.submit_many("lm", prompts, max_new=max_new)
            engine.run_until_drained()
            engine.close()
            print(f"  [{label}] {ARCHS[kind]} reduced: "
                  f"{time.perf_counter() - t0:.1f}s", flush=True)
            check(all(q.done for q in queries)
                  and engine.compile_count == c0,
                  f"{label}: {len(queries)} requests answered, 0 "
                  f"steady-state recompiles")
            firsts = []
            for q, (want, margins, steps) in zip(queries, refs):
                bad = np.nonzero(np.asarray(q.tokens) != want)[0]
                firsts.append(None if bad.size == 0 else (
                    int(bad[0]), margins[bad[0]], steps[bad[0]]))
            differ = [f for f in firsts if f is not None]
            check(all(m <= s for _, m, s in differ),
                  f"{label}: streams equal the jit(decode_step) loop up "
                  f"to a bf16 near-tie ({len(differ)} of {len(firsts)} "
                  f"part, at (token, loop margin, bf16 step) {differ})")


def four_chip_phase(data, params, nodes, workdir: Path) -> None:
    """Sharded serving over a 4-TPU mesh: SPMD vs host executor, served
    batches vs the single-host session."""
    import numpy as np
    from repro.launch.mesh import make_shard_mesh
    from repro.serve import ShardedServeEngine
    mesh = make_shard_mesh(N_SHARDS)
    check(mesh is not None and mesh.devices.size == N_SHARDS
          and all(d.platform == PLATFORM for d in mesh.devices.flat),
          f"make_shard_mesh({N_SHARDS}) spans {N_SHARDS} {PLATFORM} devices")
    store = make_store(data, params, workdir)
    t0 = time.perf_counter()
    spmd = store.sharded_session(GRAPH, MODEL, N_SHARDS, mesh=mesh,
                                 executor="spmd")
    host = store.sharded_session(GRAPH, MODEL, N_SHARDS, mesh=mesh,
                                 executor="host")
    got, want = spmd.full_logits(), host.full_logits()
    print(f"  [sharded] P={N_SHARDS} sessions + full passes in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    check(np.array_equal(got, want),
          "spmd full_logits() bit-exact vs the host executor")
    engine = ShardedServeEngine(store, N_SHARDS, max_batch=BATCH,
                                mode="subgraph", mesh=mesh, executor="spmd")
    serve(engine, nodes, "sharded/subgraph")
    single = store.session(GRAPH, MODEL)
    batches = list(engine.batch_log)
    check(sum(len(b) for b in batches) == nodes.size and all(
        np.array_equal(np.stack([q.logits for q in b]),
                       single.serve_subgraph(np.asarray([q.node for q in b])))
        for b in batches),
        f"{len(batches)} served batches bit-exact vs the single-host "
        f"session replaying batch_log")


def pallas_phase(data, params, nodes, workdir: Path, phase_a) -> None:
    """Phase B: phase A on the Pallas kernels, checked against phase A."""
    import numpy as np
    sub_b, full_b, sess_b = single_chip_phase(data, params, nodes, workdir,
                                              use_pallas=True)
    hlo_has_kernel(sess_b, nodes)
    if phase_a is None:
        check(False, "phase A's logits to compare against")
        return
    sub_a, full_a, _ = phase_a
    scale = float(np.abs(full_a).max())
    for got, want, path in ((sub_b, sub_a, "subgraph"),
                            (full_b, full_a, "full-cache")):
        same_predictions(want, np.argmax(got, -1),
                         f"pallas/{path} vs phase A")
        check(np.allclose(got, want, rtol=PALLAS_RTOL,
                          atol=PALLAS_ATOL * scale),
              f"pallas/{path} logits allclose to phase A (rtol "
              f"{PALLAS_RTOL}, atol {PALLAS_ATOL} x max|logit|; max |d| "
              f"{float(np.abs(got - want).max()):.3g})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded serving phase")
    args = ap.parse_args(argv)
    n_chips = N_SHARDS if args.four_chips else 1
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        # the CPU cross-check of phase A needs the CPU backend alongside
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    devs = require_tpu(n_chips)

    sys.path.insert(0, str(ROOT / "src"))
    import numpy as np
    from repro.env import enable_compile_cache
    cache = enable_compile_cache()
    kind = devs[0].device_kind
    print(f"device: {len(devs)} x {kind}; compile cache {cache}", flush=True)

    data, params = build()
    nodes = np.random.default_rng(1).integers(0, data.n_nodes,
                                              size=N_QUERIES)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        workdir = Path(tmp)
        if args.four_chips:
            run_phase("sharded: ShardedServeEngine, spmd executor",
                      four_chip_phase, data, params, nodes, workdir)
        else:
            phase_a = run_phase("A: jnp serving forward", single_chip_phase,
                                data, params, nodes, workdir, False)
            if phase_a is not None:
                run_phase("A: CPU backend cross-check", cpu_agreement,
                          phase_a[2], data)
            run_phase("B: Pallas BSpMM kernels", pallas_phase, data, params,
                      nodes, workdir, phase_a)
            run_phase("B: BSpMM kernels vs jnp", kernel_agreement, data)
            run_phase("C: token tier", token_phase)
    if FAILURES:
        print(f"chip_smoke: {len(FAILURES)} check(s) FAILED:", file=sys.stderr)
        for what in FAILURES:
            print(f"  {what}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
