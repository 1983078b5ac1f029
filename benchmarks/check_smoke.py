"""Gate on the smoke-bench JSON: the batched-ciphertext,
hoisted-rotation, and serving-SLO rows must exist, and every
amortization layer must actually pay.

Usage: python -m benchmarks.check_smoke BENCH_smoke.json

Checks (CI runs this right after ``benchmarks.run --smoke --json``):

  1. every required row is present with a numeric ``us_per_call`` (an
     ERROR row has ``null``),
  2. per-op time of the batch-32 multiply (``us_per_call / 32``) is
     strictly lower than the batch-1 row — the whole point of the
     batched EvalPlan layer is amortizing dispatch overhead across a
     ciphertext batch, so a regression here means the serving layer's
     throughput claim no longer holds,
  3. per-key-switch time of the hoisted 8-rotation dispatch
     (``hoisted_rotate_r8 / 8``, the BSGS matvec baby-step primitive)
     is strictly lower than 8 independent synchronized ``rotate``
     dispatches (``rotate_loop_r8 / 8``) — hoisting exists to pay ONE
     digit decomposition for R rotations, so a regression here means
     the slot-linalg layer no longer amortizes anything,
  4. the serve engine's ping-pong drain (``serve_async_throughput``,
     median of paired passes — see paper_tables.serve_slo) beats the
     synchronous oracle drain on a multi-core host, where overlapping
     host staging with device compute is physically available.  On a
     single-core host the XLA CPU worker and the Python host thread
     time-share the core, overlap buys nothing, and the drains measure
     equal to timer noise — there the gate bounds the async drain's
     overhead instead (within SERVE_1CORE_TOL of sync).  Either way a
     re-serialized dispatch pipeline (eager staging in the wrapper
     path, or a donated input stack dropped while still pending, whose
     PJRT destructor blocks until the consumer runs) fails the gate:
     those bugs made the async drain strictly slower at any core
     count,
  5. the lazy-reduction A/B rows: lazy NTT/keyswitch at 2^14 must not
     lose to the eager path (within LAZY_TOL — deferred reduction
     removes conditional subtracts, so losing means the lazy stage
     loops regressed), lazy output must stay bit-identical to eager
     (``exact=OK`` in the derived column), and the autotuned batch
     tile must stay within TILE_TOL of the fixed tile=8 baseline,
  6. the offered-load sweep rows (``serve_slo_sweep_l{pct}``) are all
     present and their ``offered=`` loads strictly increase across the
     family — presence + monotonicity only, NEVER absolute latency
     (queueing percentiles on a shared CI box move with host load),
  7. the sharded-multiply row (``ckks_multiply_sharded_d4``): the
     sharded program's output must be bit-identical to the
     single-device one (``exact=OK``) on every host, and when the row
     reports ``devices=4`` (the simulated-device child ran) AND this
     host has more than one core to back those devices, the sharded
     dispatch must reach ``SHARDED_MIN_SPEEDUP``.  A 1-core host
     time-shares all 4 simulated devices on one core — no speedup is
     physically available, so only presence + exactness are gated
     there (the forced-4-device CI job runs on multi-core runners),
  8. the ML-KEM scheme rows: ``ntt_kyber_256`` present, and each
     ``mlkem_{keygen,encaps,decaps}_b64`` row must (a) carry
     ``kat=OK`` — the bench re-verifies the checked-in FIPS 203 KAT
     vectors before timing, so a wrong scheme can never post a number
     — and (b) beat its own ``b1_us=`` column per op
     (``us_per_call / 64 < b1_us``): one batched dispatch must be
     faster per op than 64 sequential single-request calls, the whole
     point of routing the scheme through the batched banks kernels,
  9. the observability rows: the ``serve_obs_overhead`` A/B row must
     show the instrumented-ON drain keeping >= OBS_TOL (0.95x) of the
     instrumented-OFF throughput — span tracing is supposed to be a
     flag check when disabled and a handful of spans per group when
     enabled, never per-request work — and, when the bench record
     carries ``trace_out`` (CI runs ``--trace-out BENCH_trace.json``),
     the trace artifact must be valid Chrome trace-event JSON whose
     events all carry ``ph``/``ts``/``dur``/``name`` and include >= 1
     span for every serve phase (screen/group/stack/dispatch/block) —
     a Perfetto-loadable timeline of the drain.
"""
from __future__ import annotations

import json
import os
import re
import sys

REQUIRED = ("ckks_multiply_b1", "ckks_multiply_b8", "ckks_multiply_b32",
            "ckks_rotate_b32", "hoisted_rotate_r8", "rotate_loop_r8",
            "keyswitch_throughput", "linalg_matvec_bsgs",
            "serve_async_throughput", "serve_sync_throughput",
            "serve_slo_p99",
            "serve_slo_sweep_l25", "serve_slo_sweep_l50",
            "serve_slo_sweep_l70", "serve_slo_sweep_l90",
            "serve_slo_sweep_l110",
            "ckks_multiply_sharded_d4",
            "ntt_lazy_2_14", "ntt_eager_2_14", "ntt_lazy_tile8_2_14",
            "keyswitch_lazy_2_14", "keyswitch_eager_2_14",
            "ntt_kyber_256", "mlkem_keygen_b64", "mlkem_encaps_b64",
            "mlkem_decaps_b64", "serve_obs_overhead")

# the ML-KEM batched rows (gate 8): batched-beats-b1 per op + kat=OK
MLKEM_ROWS = ("mlkem_keygen_b64", "mlkem_encaps_b64", "mlkem_decaps_b64")

# the sweep family in offered-load order (the monotonicity gate)
SWEEP_ROWS = ("serve_slo_sweep_l25", "serve_slo_sweep_l50",
              "serve_slo_sweep_l70", "serve_slo_sweep_l90",
              "serve_slo_sweep_l110")

# sharded-multiply speedup floor on hosts where it is physically
# available (4 simulated devices backed by > 1 real core); the ISSUE's
# acceptance bar — 4 devices over independent batch rows should scale
# well past 2x, so this is not tight
SHARDED_MIN_SPEEDUP = 2.0

# single-core async-overhead bound: paired-pass medians put the drains
# within ~2% of each other on a 1-core host; 15% headroom absorbs CI
# scheduler noise without ever passing a re-serialized pipeline (the
# destructor/eager-staging bugs cost 2-3x, not 15%)
SERVE_1CORE_TOL = 1.15

# lazy-vs-eager headroom: the variants are timed in the same paired
# pass (paper_tables._paired_time), so residual noise is small; 5%
# catches "lazy quietly became slower" without flaking on jitter
LAZY_TOL = 1.05

# autotuned-vs-fixed-tile headroom: on CPU the ref hot path ignores the
# tile and the two rows measure the same dispatch; on TPU a tuned tile
# losing >10% to the static default means the autotuner picked a dud
TILE_TOL = 1.10

# observability overhead floor: the instrumented-ON async drain must
# keep at least this fraction of the instrumented-OFF throughput
# (equivalently: on_wall <= off_wall / OBS_TOL).  The disabled path is
# one flag check per probe; the enabled path records a handful of spans
# per group — a real regression here means instrumentation moved onto a
# per-request or per-element path
OBS_TOL = 0.95

# each serve phase must appear as >= 1 span in the captured trace
# artifact (the screen -> group -> stack -> dispatch -> block pipeline
# the PR 10 tentpole instruments; plan.stack is the EvalPlan staging
# span nested under serve.dispatch)
TRACE_PHASES = ("serve.screen", "serve.group", "plan.stack",
                "serve.dispatch", "serve.block")


def per_op_us(row: dict) -> float:
    """us_per_call is one batched dispatch; the batch size rides in the
    row name's ``_b{B}`` suffix."""
    b = int(re.search(r"_b(\d+)$", row["name"]).group(1))
    return row["us_per_call"] / b


def check(path: str) -> int:
    with open(path) as f:
        rec = json.load(f)
    rows = {r["name"]: r for r in rec.get("rows", [])}
    bad = False
    for name in REQUIRED:
        row = rows.get(name)
        if row is None or not isinstance(row.get("us_per_call"), (int, float)):
            print(f"check_smoke: FAIL — row {name!r} missing or errored "
                  f"({row.get('derived') if row else 'absent'})")
            bad = True
    if bad:
        return 1
    b1 = per_op_us(rows["ckks_multiply_b1"])
    b32 = per_op_us(rows["ckks_multiply_b32"])
    print(f"check_smoke: multiply per-op b1={b1:.1f}us b32={b32:.1f}us "
          f"(x{b1 / b32:.2f} amortization)")
    if not b32 < b1:
        print("check_smoke: FAIL — batch-32 multiply is not faster per op "
              "than batch-1; the batched dispatch layer regressed")
        return 1
    hoisted = rows["hoisted_rotate_r8"]["us_per_call"] / 8
    loop = rows["rotate_loop_r8"]["us_per_call"] / 8
    print(f"check_smoke: rotate per-keyswitch hoisted={hoisted:.1f}us "
          f"loop={loop:.1f}us (x{loop / hoisted:.2f} hoisting amortization)")
    if not hoisted < loop:
        print("check_smoke: FAIL — the hoisted 8-rotation dispatch is not "
              "faster per key switch than 8 independent rotates; the "
              "hoisted-rotation subsystem regressed")
        return 1
    t_async = rows["serve_async_throughput"]["us_per_call"]
    t_sync = rows["serve_sync_throughput"]["us_per_call"]
    cores = os.cpu_count() or 1
    print(f"check_smoke: serve drain async={t_async:.0f}us "
          f"sync={t_sync:.0f}us (x{t_sync / t_async:.2f}, {cores} cores)")
    if cores > 1:
        if not t_async < t_sync:
            print("check_smoke: FAIL — the ping-pong drain is not faster "
                  "than the synchronous drain on a multi-core host; the "
                  "async serve pipeline is no longer overlapping host "
                  "staging with device compute")
            return 1
    elif not t_async < SERVE_1CORE_TOL * t_sync:
        print(f"check_smoke: FAIL — async drain is >{SERVE_1CORE_TOL:.2f}x "
              "the sync drain on a single-core host; the dispatch "
              "pipeline has re-serialized (eager staging or a pending "
              "donated stack dropped in the wrapper path)")
        return 1
    nl = rows["ntt_lazy_2_14"]["us_per_call"]
    ne = rows["ntt_eager_2_14"]["us_per_call"]
    n8 = rows["ntt_lazy_tile8_2_14"]["us_per_call"]
    kl = rows["keyswitch_lazy_2_14"]["us_per_call"]
    ke = rows["keyswitch_eager_2_14"]["us_per_call"]
    print(f"check_smoke: lazy ntt={nl:.0f}us eager={ne:.0f}us "
          f"(x{ne / nl:.2f}); keyswitch lazy={kl:.0f}us eager={ke:.0f}us "
          f"(x{ke / kl:.2f}); tuned-vs-tile8 x{n8 / nl:.2f}")
    for name, lazy_t, eager_t in (("NTT", nl, ne), ("keyswitch", kl, ke)):
        if not lazy_t < LAZY_TOL * eager_t:
            print(f"check_smoke: FAIL — lazy {name} is >{LAZY_TOL:.2f}x the "
                  "eager path; deferred reduction is supposed to REMOVE "
                  "conditional subtracts from the stage loops")
            return 1
    if "exact=OK" not in str(rows["ntt_lazy_2_14"]["derived"]) or \
            "exact=OK" not in str(rows["keyswitch_lazy_2_14"]["derived"]):
        print("check_smoke: FAIL — lazy output is not bit-identical to "
              "eager; the epilogue reduction contract is broken")
        return 1
    if not nl < TILE_TOL * n8:
        print(f"check_smoke: FAIL — the autotuned tile is >{TILE_TOL:.2f}x "
              "the fixed tile=8 baseline; the autotuner picked a dud "
              "(or the cache/pin fed it a stale entry)")
        return 1
    # 6. offered-load sweep: loads must strictly increase across the family
    offered = []
    for name in SWEEP_ROWS:
        m = re.search(r"offered=([0-9.]+)", str(rows[name]["derived"]))
        if m is None:
            print(f"check_smoke: FAIL — sweep row {name!r} carries no "
                  "offered= load in its derived column")
            return 1
        offered.append(float(m.group(1)))
    print("check_smoke: slo sweep offered loads "
          + " -> ".join(f"{x:.1f}" for x in offered) + " req/s")
    if not all(a < b for a, b in zip(offered, offered[1:])):
        print("check_smoke: FAIL — the offered-load sweep is not "
              "monotonically increasing; the sweep bench is not "
              "actually sweeping load")
        return 1
    # 7. sharded multiply: bit-exact always; >= 2x only where available
    sh = rows["ckks_multiply_sharded_d4"]
    if str(sh["derived"]).startswith("NOT RUN"):
        print(f"check_smoke: sharded multiply {sh['derived']}")
    elif "exact=OK" not in str(sh["derived"]):
        print("check_smoke: FAIL — sharded multiply output is not "
              "bit-identical to the single-device program")
        return 1
    m_dev = re.search(r"devices=(\d+)", str(sh["derived"]))
    m_spd = re.search(r"speedup=x([0-9.]+)", str(sh["derived"]))
    devices = int(m_dev.group(1)) if m_dev else 1
    speedup = float(m_spd.group(1)) if m_spd else 1.0
    print(f"check_smoke: sharded multiply devices={devices} "
          f"speedup=x{speedup:.2f} ({cores} cores)")
    if devices == 4 and cores > 1 and speedup < SHARDED_MIN_SPEEDUP:
        print(f"check_smoke: FAIL — 4-device sharded multiply reached only "
              f"x{speedup:.2f} (< x{SHARDED_MIN_SPEEDUP:.1f}) on a "
              f"{cores}-core host; the sharded dispatch is not scaling "
              "over the batch axis")
        return 1
    # 8. ML-KEM: kat=OK on every batched row, batched beats b1 per op
    for name in MLKEM_ROWS:
        row = rows[name]
        derived = str(row["derived"])
        if "kat=OK" not in derived:
            print(f"check_smoke: FAIL — {name} does not report kat=OK; the "
                  "scheme no longer reproduces the checked-in FIPS 203 "
                  "vectors and its throughput numbers are meaningless")
            return 1
        m_b1 = re.search(r"b1_us=([0-9.]+)", derived)
        if m_b1 is None:
            print(f"check_smoke: FAIL — {name} carries no b1_us= baseline "
                  "in its derived column")
            return 1
        per = per_op_us(row)
        b1_op = float(m_b1.group(1))
        print(f"check_smoke: {name} per-op b64={per:.1f}us b1={b1_op:.1f}us "
              f"(x{b1_op / per:.2f} amortization)")
        if not per < b1_op:
            print(f"check_smoke: FAIL — {name} is not faster per op than "
                  "64 sequential b=1 calls; the batched ML-KEM dispatch "
                  "layer regressed")
            return 1
    # 9. observability: enabled-vs-disabled drain overhead + trace artifact
    row = rows["serve_obs_overhead"]
    t_on = row["us_per_call"]
    m_off = re.search(r"off=([0-9.]+)us", str(row["derived"]))
    if m_off is None:
        print("check_smoke: FAIL — serve_obs_overhead carries no off= "
              "baseline in its derived column")
        return 1
    t_off = float(m_off.group(1))
    print(f"check_smoke: obs overhead on={t_on:.0f}us off={t_off:.0f}us "
          f"(x{t_on / t_off:.3f}, floor {OBS_TOL:.2f}x throughput)")
    if not t_on <= t_off / OBS_TOL:
        print(f"check_smoke: FAIL — the instrumented drain keeps only "
              f"{t_off / t_on:.2f}x of the uninstrumented throughput "
              f"(< {OBS_TOL:.2f}x); span tracing / metrics mirroring has "
              "grown real per-request cost")
        return 1
    trace_path = rec.get("trace_out")
    if trace_path:
        if not os.path.isabs(trace_path) and not os.path.exists(trace_path):
            trace_path = os.path.join(os.path.dirname(os.path.abspath(path)),
                                      trace_path)
        try:
            with open(trace_path) as f:
                trace = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"check_smoke: FAIL — trace artifact {trace_path!r} is "
                  f"not loadable JSON ({e})")
            return 1
        evs = trace.get("traceEvents")
        if not isinstance(evs, list) or not evs:
            print("check_smoke: FAIL — trace artifact carries no "
                  "traceEvents (not a Chrome trace-event capture)")
            return 1
        bad_evs = [e for e in evs
                   if not all(k in e for k in ("ph", "ts", "dur", "name"))]
        if bad_evs:
            print(f"check_smoke: FAIL — {len(bad_evs)} trace events are "
                  "missing required ph/ts/dur/name fields (Perfetto would "
                  "reject or misrender them)")
            return 1
        names = [str(e["name"]) for e in evs]
        missing = [ph for ph in TRACE_PHASES
                   if not any(n == ph for n in names)]
        if missing:
            print(f"check_smoke: FAIL — trace artifact has no span for "
                  f"serve phase(s) {missing}; the drain pipeline is no "
                  "longer fully instrumented")
            return 1
        print(f"check_smoke: trace artifact OK — {len(evs)} spans, every "
              f"phase of {'/'.join(p.split('.')[-1] for p in TRACE_PHASES)} "
              "present")
    else:
        print("check_smoke: note — no trace_out in the bench record; "
              "trace-artifact phase gate skipped (run with --trace-out)")
    print("check_smoke: OK")
    return 0


if __name__ == "__main__":
    if len(sys.argv) != 2:
        print(__doc__)
        sys.exit(2)
    sys.exit(check(sys.argv[1]))
