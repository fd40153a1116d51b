#!/usr/bin/env python3
"""Readings for a cell's limits, taken on the chip at the cell's own size.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s> [--controls 3]

For each seed, in ONE process (set-up is most of a run): the program's own
readings (the lower reading of a limit is the largest of these over a
dozen seeds) and, on the first ``--controls`` seeds, the same comparison
with the driver's controls and planted faults in the program's place (the
upper reading is the smallest of these).  One JSON line per seed.  PERF.md
section 2 holds what this printed and the limits set from it; no benchmark
run calls it."""
import argparse
import json
import sys
import time

import run


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        cell = run.load_cell(args.workload, seed, args.rehearse)
        driver = run.prepare(cell)
        session = driver.Session(cell)
        session.setup()
        session.measure(args.seconds)
        session.release()
        out = {"workload": args.workload, "seed": seed,
               "device": cell["device"],
               "program": session.check()}
        if i < args.controls:
            out.update(driver.control_readings(session))
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
