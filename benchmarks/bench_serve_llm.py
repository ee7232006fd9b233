"""Token-serving benchmark: QPS / latency percentiles / time-to-first-token
of the token serving tier (TokenStore -> TokenSession -> TokenServeEngine)
for the binary transformer and the RWKV SSM stack.

Queries arrive in waves (one micro-batch worth, then tick) like the GNN
serve bench, so latency is end-to-end batch service time. Two recorded
gates ride along: ``steady_state_compiles`` (the zero-recompile invariant
after warmup, zero-tolerance in ``compare_bench``) and ``bit_exact`` (a
sample of served streams replayed through the direct ``jit(decode_step)``
loop). Emits CSV rows plus ``results/BENCH_serve_llm.json``.
"""
from __future__ import annotations

import gc
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.models import transformer
from repro.serve import TokenServeEngine, TokenStore

from .common import csv_row

RESULTS = Path(__file__).resolve().parents[1] / "results"

# bump when the emitted JSON layout changes (compare_bench.py warns on
# cross-version diffs)
SCHEMA_VERSION = 1

ARCHS = {"transformer": "stablelm-1.6b", "ssm": "rwkv6-3b"}


def _direct_reference(cfg, params, prompt, max_new):
    """The oracle: python loop of jit(decode_step) with argmax feedback."""
    step = jax.jit(lambda p, c, t, pos: transformer.decode_step(
        p, cfg, c, t, pos))
    total = prompt.size + max_new
    cache = transformer.init_cache(
        cfg, 1, max(64, int(2 ** np.ceil(np.log2(total)))))
    out, prev = [], None
    for t in range(prompt.size + max_new - 1):
        tok = prompt[t] if t < prompt.size else prev
        lg, cache = step(params, cache, jnp.asarray([[tok]], jnp.int32), t)
        prev = int(np.argmax(np.asarray(lg[0, 0, :cfg.vocab])))
        if t >= prompt.size - 1:
            out.append(prev)
    return np.asarray(out[:max_new], np.int32)


def _pct_ms(vals, q):
    return float(np.percentile(np.asarray(vals), q) * 1e3) if vals else 0.0


def _bench_family(kind: str, n_queries: int, batch: int, max_new: int,
                  chunk: int, pipeline_depth: int = 1, seed: int = 0,
                  oracle_samples: int = 4) -> dict:
    cfg = reduced_config(get_config(ARCHS[kind])).resolve_for_mesh(tp=1)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    store = TokenStore(max_batch=batch, max_len=256, chunk=chunk,
                       warm_len=12, warm_new=max_new)
    store.register_model("lm", cfg, params)
    eng = TokenServeEngine(store, pipeline_depth=pipeline_depth)
    warm_compiles = eng.warmup("lm")
    c0 = eng.compile_count

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab,
                            int(rng.integers(3, 12))).astype(np.int32)
               for _ in range(n_queries)]
    queries = []
    gc.collect()
    gc_was = gc.isenabled()
    gc.disable()
    t0 = time.perf_counter()
    try:
        for i in range(0, n_queries, batch):
            queries += eng.submit_many("lm", prompts[i:i + batch],
                                       max_new=max_new)
            eng.tick()
        eng.run_until_drained()
    finally:
        if gc_was:
            gc.enable()
    wall_s = time.perf_counter() - t0
    snap = eng.snapshot()
    steady_compiles = eng.compile_count - c0
    eng.close()

    answered = [q for q in queries if q.done]
    tokens_out = int(sum(q.tokens.size for q in answered))
    ttfts = [q.ttft_s for q in answered if q.ttft_s > 0]
    sample = answered[:: max(1, len(answered) // max(oracle_samples, 1))]
    sample = sample[:oracle_samples]
    bit_exact = all(
        np.array_equal(q.tokens,
                       _direct_reference(cfg, params, q.prompt, max_new))
        for q in sample)
    lat = snap["latency"]
    return dict(
        arch=ARCHS[kind], n_queries=n_queries, batch=batch,
        max_new=max_new, chunk=chunk, pipeline_depth=pipeline_depth,
        qps=snap["qps"],
        tokens_per_s=tokens_out / max(wall_s, 1e-9),
        tokens_generated=tokens_out,
        latency=lat,
        ttft_p50_ms=_pct_ms(ttfts, 50),
        ttft_p99_ms=_pct_ms(ttfts, 99),
        warmup_compiles=warm_compiles,
        steady_state_compiles=steady_compiles,
        dropped_queries=n_queries - len(answered),
        bit_exact=bool(bit_exact),
        oracle_samples=len(sample),
        family_label=snap["family"],
    )


def run(full: bool = False) -> dict:
    n_queries = 64 if full else 24
    batch = 8 if full else 4
    max_new = 16 if full else 8
    chunk = 8 if full else 4

    summary: dict = dict(schema_version=SCHEMA_VERSION,
                         n_queries=n_queries, batch=batch,
                         max_new=max_new, chunk=chunk, families={})
    for kind in sorted(ARCHS):
        sec = _bench_family(kind, n_queries, batch, max_new, chunk)
        summary["families"][kind] = sec
        lat = sec["latency"]
        csv_row(f"serve_llm/{kind}",
                1e6 / max(sec["qps"], 1e-9),
                f"qps={sec['qps']:.1f};tok_s={sec['tokens_per_s']:.0f};"
                f"p50_ms={lat['p50_ms']:.2f};p99_ms={lat['p99_ms']:.2f};"
                f"ttft_p50_ms={sec['ttft_p50_ms']:.2f};"
                f"steady_compiles={sec['steady_state_compiles']};"
                f"dropped={sec['dropped_queries']};"
                f"bit_exact={sec['bit_exact']}")

    RESULTS.mkdir(parents=True, exist_ok=True)
    out = RESULTS / "BENCH_serve_llm.json"
    out.write_text(json.dumps(summary, indent=2))
    csv_row("serve_llm/summary", 0.0, f"wrote={out}")
    return summary


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    run(full=args.full)
