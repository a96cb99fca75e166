"""Readings that set the limits of ``correct``: program and control.

    python chipbench/control.py <cell> --seeds 1 2 3 ... --seconds 15

Run on the chip, at the cell's own size and load, never by the
benchmark's own runs.  Each seed is one whole run (``run.measure``:
set-up, a window of the cell's traffic, the reference check), with the
controls read beside the program: the reference computed in bfloat16,
and in float8, put in the program's place, at the same prompts and
tokens.  Per seed it
prints whether the program and the control pass the cell's limits, and
every number read from each side.  One process serves every seed, so
set-up compiles once.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import cells, run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    cell = cells.cell(args.workload)
    device = run.setup_process(cell.chips)
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = run.run_cell(cell, seed, args.seconds, False, device, t0,
                           control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "control_correct": {p: c["correct"] for p, c
                                              in out["control"].items()},
                          "readings": out["readings"],
                          "attempted": out["attempted"],
                          "pool_pages": out["pool_pages"],
                          "memory_peak_bytes":
                              out["device"]["memory_peak_bytes"],
                          "wall_s": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    main()
