"""Readings behind a cell's correctness limits, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 40 [--faults one_answer_altered,answers_shifted \
        --fault-seconds 10]

For each seed, one run of the cell as it is timed, whose answers are
compared with the reference; the same answers' reference rows computed in
bfloat16 (the control: the reference one precision step below the
configuration's float32) are compared beside them and judged against the
cell's limits as a run's answers are (``control_correct``). Then, on each seed,
one run per planted fault (``chipbench/faults.py``). One JSON line per
run; the benchmark's own runs never do this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import faults, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seconds", type=float, default=None)
    args = ap.parse_args(argv)
    run.configure_jax()
    seeds = [int(s) for s in args.seeds.split(",")]
    names = [f for f in args.faults.split(",") if f]
    for seed in seeds:
        for fault in [None] + names:
            try:
                secs = args.seconds if fault is None else (
                    args.fault_seconds or args.seconds)
                res = run.run_cell(
                    args.workload, seed, secs, False,
                    patch=faults.FAULTS[fault] if fault else None,
                    control=fault is None)
            except run.NoAccelerator as e:
                print(f"chipbench: {e}", file=sys.stderr)
                return 2
            info = res["_info"]
            print(json.dumps(dict(
                workload=args.workload, seed=seed, fault=fault,
                correct=res["correct"], program=info["compared"],
                control=info.get("control"),
                control_correct=info.get("control_correct"),
                counts=info["counts"]),
                default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
