#!/usr/bin/env python3
"""Offered-load sweep of an open-loop cell, in one process on one chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates <r1> <r2> ...

Builds the cell's deployment once, then runs one open-loop window per
rate (the cell's traffic with only the rate changed) and prints one JSON
line per rate: offered and completed rate, p50 and p99 latency, peak
queue depth, and how long after the last due time the last answer came
(``lag_s``).  The knee is the highest rate of an ascending ladder up to
which every window drained within 5% of its length after its last due
time, with no failed request: a queue that grows through the window
leaves a lag of the order of the window; the ladder stops at its second
failing rate.  The benchmark's cells fix their rate from this sweep;
their runs never search for one.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

import run
from harness import arith, serving, spec, traffic as gen


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--write-fraction", type=float,
                    help="write this fraction of the knee into the cell's "
                         "traffic file as its rate")
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    cell = spec.find_workload(bench, args.workload)
    run.enable_compile_cache()
    try:
        run.device_info(int(cell["chips"]), spec.load_peaks())
    except run.DeviceError as e:
        print(f"bench/sweep.py: {e}", file=sys.stderr)
        return 2
    config = spec.load_config(cell["config"])
    base = spec.load_traffic(cell["traffic"])
    t0 = time.perf_counter()
    system = run.make_system(config, base, args.seed)
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    knee, ok, fails = None, True, 0
    for i, r in enumerate(args.rates):
        traffic = copy.deepcopy(base)
        traffic["arrivals"]["rate"] = r
        reqs, arrivals = gen.open_loop(traffic, args.seed + i, args.seconds)
        fhe = [system.fhe_request(q) for q in reqs]
        win, _ = serving.run_open(system.engine, fhe, arrivals, set())
        lat = win["latencies_s"]
        lag = win["last_drain"] - (win["t0"] + float(arrivals[-1]))
        ok = ok and lag <= 0.05 * args.seconds and not win["engine"]["failed"]
        knee = r if ok else knee
        print(json.dumps({
            "rate": r, "requests": win["attempted"],
            "completed_per_s": arith.rate(win["completed"], win["wall_s"]),
            "p50_ms": arith.percentile(lat, 50) * 1e3,
            "p99_ms": arith.percentile(lat, 99) * 1e3,
            "max_queue": system.engine.stats.get("max_queue"),
            "lag_s": lag,
            "rows_per_dispatch": (win["engine"]["batched_ops"]
                                  / max(1, win["engine"]["dispatches"])),
            "failed": win["engine"]["failed"]}), flush=True)
        fails += not ok
        if fails >= 2:
            break
    print(json.dumps({"knee": knee}), flush=True)
    if args.write_fraction and knee:
        path = os.path.join(spec.BENCH_DIR, "traffic", f"{cell['traffic']}.json")
        base["arrivals"]["rate"] = round(args.write_fraction * knee, 1)
        with open(path, "w") as f:
            json.dump(base, f, indent=2)
            f.write("\n")
        print(json.dumps({"written_rate": base["arrivals"]["rate"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
