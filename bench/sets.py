#!/usr/bin/env python3
"""Runs a cell several times, one fresh process per run, and reports the
spread of each metric.

    python3 bench/sets.py --workload <cell> --seconds <s> --trace <0|1> \
        --seeds <n1> <n2> ... [--out results.jsonl]

Each run is ``python3 bench/run.py`` in a child process; this parent
never imports JAX, so the child has the chip to itself.  Every result
line (with the run's seed, exit code and the tail of its standard error)
is appended to ``--out``; the summary gives, per metric, the median and
the interquartile spread over the median (``statistics.quantiles``).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

from harness import arith

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    values: dict = {}
    bad = 0
    for seed in args.seeds:
        t0 = time.perf_counter()
        p = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload",
             args.workload, "--seed", str(seed), "--seconds", str(args.seconds),
             "--trace", str(args.trace)],
            capture_output=True, text=True)
        lines = p.stdout.strip().splitlines()
        try:
            res = json.loads(lines[-1]) if p.returncode == 0 else None
        except (IndexError, json.JSONDecodeError):
            res = None
        rec = {"workload": args.workload, "seed": seed, "rc": p.returncode,
               "process_s": time.perf_counter() - t0, "result": res,
               "stderr_tail": p.stderr[-1500:]}
        print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
        if res is None or not res["correct"]:
            bad += 1
            continue
        for name, m in res["metrics"].items():
            values.setdefault(name, []).append(m["value"])
    for name, vs in values.items():
        line = {"metric": name, "n": len(vs), "median": statistics.median(vs)}
        if len(vs) >= 2:
            line["spread"] = arith.spread(vs)
        print(json.dumps(line), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
