"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus a roofline summary row per
dry-run cell if experiments/dryrun JSONs exist).

Usage: PYTHONPATH=src python -m benchmarks.run [--skip-slow | --smoke]
                                               [--json PATH]

``--smoke`` runs the fast CI subset (NTT-128, the bank-parallel
keyswitch throughput datapoints, the EvalPlan ckks_multiply /
ckks_rotate scheme-op rows, the ciphertext-batched
ckks_multiply_b{1,8,32} / ckks_rotate_b32 rows, the hoisted-rotation
rows incl. the projected-vs-measured keyswitch_throughput datapoint,
the serving SLO rows: async/sync drain walls over a seeded mixed
trace plus p50/p99 request latency under Poisson arrivals, and the
lazy-vs-eager reduction A/B rows at the paper's 2^14 ring).  Every mode
exits nonzero on any ERROR row.  The persistent compilation cache goes
to ``$JAX_COMPILATION_CACHE_DIR`` or ``<repo>/.jax_cache``
(``repro.compile_cache``).  ``--json PATH`` additionally writes the
rows as a JSON record plus a ``*_autotune.json`` sibling snapshotting
the batch-tile tuning state — CI uploads the smoke run's files as
``BENCH_*.json`` artifacts so a bench trajectory accumulates across
PRs, then gates it through ``benchmarks.check_smoke`` (batch-32
multiply must beat batch-1 per op; the hoisted 8-rotation dispatch
must beat 8 independent rotates per key switch; the ping-pong serve
drain must beat the synchronous drain on multi-core hosts and stay
within a bounded overhead of it on single-core hosts; lazy must not
lose to eager and the autotuned tile must not lose to the fixed
tile=8 baseline).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import platform
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-slow", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset; nonzero exit on any ERROR row")
    ap.add_argument("--scaling", action="store_true",
                    help="device-scaling subset (ntt-aie-shaped table + "
                         "offered-load sweep) — the forced-4-device CI "
                         "job's BENCH_scaling.json")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as a JSON record (bench trajectory)")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="enable repro.obs for the run and write a "
                         "Perfetto-loadable Chrome trace-event JSON of "
                         "every instrumented span (plus a *_metrics.json "
                         "sibling snapshotting the metrics registry)")
    args = ap.parse_args()

    from repro import compile_cache
    compile_cache.enable()

    if args.trace_out:
        # capture the whole run: the serve_obs_overhead row toggles the
        # flag around its paired passes and restores it, so the capture
        # survives; the enabled overhead is CI-gated at <= 5%
        from repro import obs
        obs.clear()
        obs.reset()
        obs.enable()

    from benchmarks import paper_tables
    fns = (paper_tables.SCALING if args.scaling
           else paper_tables.SMOKE if args.smoke else paper_tables.ALL)
    failed = False
    rows = []
    print("name,us_per_call,derived")
    for fn in fns:
        if args.skip_slow and fn.__name__ in ("fig22_keyswitch",):
            continue
        try:
            for name, us, derived in fn():
                rows.append({"name": name, "us_per_call": us, "derived": derived})
                print(f"{name},{us:.2f},{derived}")
        except Exception as e:  # keep the harness running
            failed = True
            rows.append({"name": fn.__name__, "us_per_call": None,
                         "derived": f"ERROR: {type(e).__name__}: {e}"})
            print(f"{fn.__name__},NaN,ERROR: {type(e).__name__}: {e}")
    if args.trace_out:
        from repro import obs
        obs.write_trace(args.trace_out)
        metrics_path = os.path.splitext(args.trace_out)[0] + "_metrics.json"
        obs.write_metrics(metrics_path)
        print(f"# wrote {len(obs.events())} spans to {args.trace_out} "
              f"(+ metrics snapshot {metrics_path}) — load the trace at "
              "https://ui.perfetto.dev", file=sys.stderr)
    if args.json:
        rec = {"suite": ("scaling" if args.scaling
                         else "smoke" if args.smoke else "all"),
               "unix_time": int(time.time()),
               "platform": platform.platform(),
               "git": os.environ.get("GITHUB_SHA", ""),
               "trace_out": args.trace_out,
               "rows": rows}
        with open(args.json, "w") as f:
            json.dump(rec, f, indent=2)
        print(f"# wrote {len(rows)} rows to {args.json}", file=sys.stderr)
        # snapshot the batch-tile tuning state (env pin + every cached
        # (backend, family, k, n, b) -> tile entry) next to the rows so
        # the CI artifact records WHICH tiles produced them
        from repro.kernels import autotune
        tile_path = os.path.splitext(args.json)[0] + "_autotune.json"
        autotune.dump(tile_path)
        print(f"# wrote autotune table to {tile_path}", file=sys.stderr)
    if failed:
        sys.exit(1)

    # roofline summaries from the dry-run sweep (if present)
    pat = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "dryrun", "*.json")
    for path in sorted(glob.glob(pat)):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if "skipped" in rec:
            print(f"dryrun_{rec['arch']}_{rec['shape']}_{rec['mesh']},0.00,"
                  f"SKIP({rec['skipped'][:40]})")
            continue
        rl = rec["roofline"]
        print(f"dryrun_{rec['arch']}_{rec['shape']}_{rec['mesh']},0.00,"
              f"dom={rl['dominant']} c={rl['compute_s']:.4f}s m={rl['memory_s']:.4f}s "
              f"coll={rl['collective_s']:.4f}s useful={rl['useful_ratio']:.3f}")


if __name__ == "__main__":
    main()
