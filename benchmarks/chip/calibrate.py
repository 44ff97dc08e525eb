"""Readings that the limits of ``correct`` are set from, on the chip, at
the cell's own size, in one process: the program's numbers over many
seeds (the lower readings), and on the first few seeds the fp8 control's
numbers and, for training, the half-batch fault's (the upper readings).

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,13 --control 3 --seconds 2

One JSON line per seed on standard output.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="read the control on this many of the seeds")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        result, info = run.run_cell(args.workload, seed, args.seconds, 0,
                                    t_start=t0, control=i < args.control)
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          "numbers": info["numbers"],
                          "control": info.get("control"),
                          "notes": info["notes"], "window": info["window"],
                          "setup_s": info["setup_s"],
                          "run_s": time.perf_counter() - t0},
                         default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
