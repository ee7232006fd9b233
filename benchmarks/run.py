"""Benchmark harness entry point: one section per paper table/figure.

``python -m benchmarks.run [--full]`` prints ``name,us_per_call,derived``
CSV. --full uses paper-scale datasets (slow on CPU); default is scaled."""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    from repro.env import enable_compile_cache
    enable_compile_cache()
    from . import (bench_fig4, bench_gnn_tables, bench_grad_compress,
                   bench_memory, bench_replica, bench_serve_gnn,
                   bench_serve_llm, bench_sharded_serve)
    sections = [
        ("gnn_tables", bench_gnn_tables.run),     # Tables 3, 4, 5
        ("memory", bench_memory.run),             # Peak-Mem columns
        ("fig4", bench_fig4.run),                 # kernel profile proxy
        ("grad_compress", bench_grad_compress.run),
        ("serve_gnn", bench_serve_gnn.run),       # serving QPS/latency
        ("serve_llm", bench_serve_llm.run),       # token serving tier
        ("sharded_serve", bench_sharded_serve.run),  # partitioned serving
        ("replica", bench_replica.run),           # fault-tolerant tier
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in sections:
        if args.only and args.only not in name:
            continue
        try:
            fn(full=args.full)
        except Exception:
            failures += 1
            traceback.print_exc()
    # roofline summary (reads results/dryrun if present)
    try:
        from . import roofline
        rows = roofline.load_all("single")
        for r in rows:
            rec = r["rec"]
            print(f"roofline/{rec['arch']}/{rec['shape']},0.0,"
                  f"dominant={r['dominant']};frac={r['roofline_fraction']:.3f}")
    except Exception:
        traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
