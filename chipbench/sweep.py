"""Offer one cell's traffic at several fixed rates, one run each, to find
the highest rate the program sustains (the knee) on the chip.

    python3 chipbench/sweep.py --workload <cell> --rates 2000,5000 \
        [--update-rate <per s>] --seconds 5 --seed 1

Each rate replaces the cell's ``query_rate_per_s`` for that run only; the
cell's files are not touched. A line per rate gives the rate offered, the
queries answered per second in the window, the queries left unanswered at
its close, the latency tail and the refresh time.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--update-rate", type=float, default=None)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    run.configure_jax()
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell = {"query_rate_per_s": rate}
        if args.update_rate is not None:
            cell["update_rate_per_s"] = args.update_rate
        try:
            res = run.run_cell(args.workload, args.seed + i, args.seconds,
                               False, overrides={"cell": cell})
        except run.NoAccelerator as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return 2
        info = res["_info"]
        c = info["counts"]
        print(json.dumps(dict(
            rate=rate, update_rate=args.update_rate,
            qps=c["answered_in_window"] / c["window_s"],
            backlog=c["attempted"] - c["answered_in_window"],
            latency_ms=c["latency_ms"], stale_ms=c["stale_ms"],
            lateness_ms=info["lateness_ms"], ticks_ms=info["ticks_ms"],
            correct=res["correct"], setup_s=res["metrics"]
            .get("setup_s", {}).get("value")), default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
