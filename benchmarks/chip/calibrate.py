#!/usr/bin/env python3
"""Readings that the limits of the correctness check are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,3 --seconds 3 [--precision bf16x | --reference-control]

Runs the cell once per seed in one process (programs compile once), each
run a short window at the cell's own size and load, and prints one JSON
line per seed with the numbers the check compared. The control, which the
limits must reject, is ``--precision bf16x`` where the program's own
bfloat16 path runs (MD), and ``--reference-control`` where it does not:
``control`` of ``drivers/<app>.py``, the plain reference in bfloat16 in the
program's place (VIC). Not part of a benchmark run.
"""
import argparse
import json
import sys
import time

import run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--precision", default=None)
    ap.add_argument("--reference-control", action="store_true")
    args = ap.parse_args()
    over = {"precision": args.precision} if args.precision else None
    wrap = None
    if args.reference_control:
        man = run.MF.load(run.ROOT)
        app = run.MF.Cell(man, run.ROOT, args.workload).config["app"]
        wrap = run.load_module("drivers", app).control
    label = ("reference-bf16" if args.reference_control
             else args.precision or "config")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            res = run.run_cell(args.workload, seed, args.seconds, False,
                               t_start=t0, config_over=over, wrap=wrap,
                               log=lambda *a, **k: None)
        except run.Refused as e:
            print(f"calibrate.py: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"seed": seed, "precision": label,
                          "steps": res["attempted"],
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
