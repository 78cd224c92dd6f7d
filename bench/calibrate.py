#!/usr/bin/env python3
"""Calibration runs of one cell, several in one process on the chip.

  python3 bench/calibrate.py readings --workload nemo-12b.doc-qa \\
      --seeds 11,12,13 --seconds 20
  python3 bench/calibrate.py sweep --workload granite-8b.chat \\
      --rates 1.6,2,2.5 --schedules 1,2,3 --seconds 51 --seed 5

``readings`` prints, for each seed, the program's widest logit gap and
the float8 control's over the same sample: the two readings a limit is
set between.  ``sweep`` serves the cell at each arrival rate under each
arrival schedule (the mix's ``schedule_seed`` replaced), each point on
a seed of its own counted up from ``--seed``, and prints the tails and
the queue left at the window's close: the knee is the highest rate
whose queue does not grow.  Neither is part of the benchmark's own
runs.
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                     "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--schedules", default="1")
    args = ap.parse_args(argv)
    from bench import harness
    if args.mode == "readings":
        runs = [dict(seed=int(s), control=True)
                for s in args.seeds.split(",")]
    else:
        points = [(float(r), int(q)) for r in args.rates.split(",")
                  for q in args.schedules.split(",")]
        runs = [dict(seed=args.seed + i, rate=r, schedule_seed=q)
                for i, (r, q) in enumerate(points)]
    for kw in runs:
        t = time.perf_counter()
        out = harness.run_cell(args.workload, seconds=args.seconds,
                               trace=False, **kw)
        line = dict(kw, correct=out["correct"], attempted=out["attempted"],
                    failed=out["failed"], metrics={
                        k: v["value"] for k, v in out["metrics"].items()},
                    notes=out["notes"], checks=out["checks"],
                    wall_s=time.perf_counter() - t)
        print("CALIBRATE " + json.dumps(line), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
