#!/usr/bin/env python3
"""Reads the program and the control of a cell on the chip, seed by seed
in one process: the cell's own set-up and a short window at its own
load, then the check of the program's answers and the same check with
the reference's control in the program's place (CKKS: division by P
floored, not rounded; ML-KEM: Compress floored, not rounded).  One line
per seed, with both sets of readings.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run
from harness import spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.find_workload(bench, args.workload)
    run.enable_compile_cache()
    try:
        device = run.device_info(int(cell["chips"]), spec.load_peaks())
    except run.DeviceError as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 2
    for seed in args.seeds:
        res = run.run_cell(bench, cell, seed, args.seconds, False,
                           device=device, t_start=time.perf_counter(),
                           control=True)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "control_correct": res["control_correct"],
                          "control_checks": res["control_checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
