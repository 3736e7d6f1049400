#!/usr/bin/env python3
"""Readings behind the correctness limits, at a cell's own size on the chip.

    python chipbench/tools/readings.py --workload t1-20480.metropolis \
        --seeds 101,102,103 --control-seeds 201,202,203 --seconds 10

Runs the cell in one process on each seed, then the control (the program's
own lower-precision path, ``prob_dtype="bfloat16"``, the float32 uniforms
of the configuration one step down) on each control seed, and prints one
JSON line per run with every number compared. The benchmark's runs never
run it; the lower reading of a number is the largest over the sound
seeds, the upper the smallest over the control's.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402

CONTROL = {"prob_dtype": "bfloat16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    plan = [(int(s), {}) for s in args.seeds.split(",") if s]
    plan += [(int(s), CONTROL) for s in args.control_seeds.split(",") if s]
    for seed, over in plan:
        out = run.run_cell(cell, seed, args.seconds, traced=False,
                           overrides=over, t0=time.perf_counter())
        print(json.dumps({"seed": seed, "control": bool(over),
                          "correct": out["correct"],
                          "checks": {k: v["value"]
                                     for k, v in out["checks"].items()},
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
