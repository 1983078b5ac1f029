#!/usr/bin/env python3
"""Runs one benchmark cell once, on the TPU it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  Set-up builds the deployment from
the seed and warms every program the window runs; the window then runs
for ``--seconds`` on the host clock; afterwards a seeded sample of the
window's answers is compared with the plain references under
``bench/reference``.  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the
profiler and the result carries the per-layer metrics, each read by
``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each number compared with its
limit).  The run fails, printing no result, on a backend that is not a
TPU, with fewer chips than the cell asks for, or on a device kind that
``bench/peaks.json`` does not list.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from harness import arith, spec, traffic as gen  # noqa: E402


class DeviceError(RuntimeError):
    """No TPU, too few chips, or a device kind without peaks."""


def device_info(chips: int, peaks: dict) -> dict:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise DeviceError(f"default backend is {backend!r}, not a TPU")
    devs = jax.devices()
    if len(devs) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise DeviceError(f"device kind {kind!r} is not in bench/peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": chips}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache``; every program cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Counts JAX backend compilations and their seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += secs


class GcClock:
    """Counts the garbage collector's passes and their seconds."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.count += 1
            self.seconds += time.perf_counter() - self._t

    def close(self):
        gc.callbacks.remove(self._on)


def settle_heap():
    """Collects, then freezes every object made so far, so that the
    collector's passes in the window walk only what the window makes and
    not the set-up's programs, keys and requests."""
    gc.collect()
    gc.freeze()


def make_system(config: dict, traffic: dict, seed: int):
    if config["scheme"] == "ckks":
        from harness.ckks import CkksCell
        return CkksCell(config, traffic, seed)
    if config["scheme"] == "mlkem":
        from harness.mlkem import MlkemCell
        return MlkemCell(config, traffic, seed)
    raise spec.SpecError(f"unknown scheme {config['scheme']!r}")


def _sample(reqs: list, size: int, rng) -> list:
    """Seeded sample of requests: the first of each op kind (so the
    longest kind is in it), then uniform draws."""
    first: dict = {}
    for i in rng.permutation(len(reqs)):
        first.setdefault(reqs[int(i)].op, int(i))
    picked = list(dict.fromkeys(first.values()))
    rest = [i for i in rng.permutation(len(reqs)) if int(i) not in first.values()]
    picked += [int(i) for i in rest[:max(0, size - len(picked))]]
    return [reqs[i] for i in picked]


def _window(system, traffic: dict, seed: int, seconds: float,
            gclock: GcClock):
    """Runs the measured window.  Returns (window, check items); the
    window records the collector's passes in it under ``gc``."""
    import numpy as np
    from harness import serving

    loop = traffic["loop"]
    size = int(traffic["check_sample"])
    rng = np.random.default_rng([seed, 5])
    if loop == "open":
        reqs, arrivals = gen.open_loop(traffic, seed, seconds)
        sample = _sample(reqs, size, rng)
        fhe = [system.fhe_request(r) for r in reqs]
        settle_heap()
        gc0 = (gclock.count, gclock.seconds)
        win, kept = serving.run_open(system.engine, fhe, arrivals,
                                     {r.rid for r in sample})
        win["gc"] = (gclock.count - gc0[0], gclock.seconds - gc0[1])
    elif loop == "backlog":
        chunk, made = int(traffic["chunk"]), []
        req_rng = np.random.default_rng(seed)

        def next_chunk():
            reqs = gen.requests(traffic, chunk, req_rng, first_rid=len(made))
            made.extend(reqs)
            return [system.fhe_request(r) for r in reqs]

        settle_heap()
        gc0 = (gclock.count, gclock.seconds)
        win, answers = serving.run_backlog(system.engine, next_chunk, seconds)
        win["gc"] = (gclock.count - gc0[0], gclock.seconds - gc0[1])
        return win, [_item(r, answers.get(r.rid))
                     for r in _sample(made, size, rng)]
    elif loop == "closed":
        settle_heap()
        gc0 = (gclock.count, gclock.seconds)
        win, calls = system.run_closed(seconds, seed)
        win["gc"] = (gclock.count - gc0[0], gclock.seconds - gc0[1])
        # each kept call is checked on one random row of each of
        # size/len(calls) equal strata of its batch, so a fault in any
        # part of the batch is seen
        items = []
        for pairs, outs in calls:
            for s in np.array_split(np.arange(len(pairs)),
                                    max(1, size // len(calls))):
                i = int(s[rng.integers(len(s))])
                a, b = (int(x) for x in pairs[i])
                items.append(("multiply", a, 0, b, outs[i]))
        return win, items
    else:
        raise spec.SpecError(f"unknown loop {loop!r}")
    return win, [_item(r, kept.get(r.rid)) for r in sample]


def _item(req: gen.Request, answer):
    if req.op == "mlkem_encaps":
        return (req.op, req.pool, req.rid, answer)
    return (req.op, req.pool, req.r, None, answer)


def end_to_end(name: str, win: dict, setup_s: float) -> float:
    if name == "setup_s":
        return setup_s
    if name in ("req_per_s", "handshakes_per_s"):
        return arith.rate(win["completed"], win["wall_s"])
    if name == "p50_ms":
        return arith.percentile(win["latencies_s"], 50) * 1e3
    if name == "keyswitch_per_s":
        return arith.rate(win["plan"]["key_switches"], win["wall_s"])
    raise spec.SpecError(f"no end-to-end metric {name!r} in the harness")


def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             trace: bool, *, device: dict | None, t_start: float = T_START,
             bench_dir: str = spec.BENCH_DIR, config: dict | None = None,
             traffic: dict | None = None, control: bool = False) -> dict:
    """One run of one cell.  ``device`` None skips the look for a chip
    (tests on the CPU); ``config``/``traffic`` override the files;
    ``control`` also checks the reference's control in the program's
    place, under ``control_correct`` and ``control_checks``
    (``bench/control.py``; never in a benchmark run)."""
    import jax

    clock = CompileClock()
    gclock = GcClock()
    config = config or spec.load_config(cell["config"], bench_dir)
    traffic = traffic or spec.load_traffic(cell["traffic"], bench_dir)
    system = make_system(config, traffic, seed)
    setup_s = time.perf_counter() - t_start
    setup_compiles, setup_compile_s = clock.count, clock.seconds
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    from repro import obs
    try:
        if trace:
            from harness import trace as tr
            obs.clear()
            obs.enable(forward_to_jax=True)
            with tr.capture(log_dir):
                with jax.profiler.TraceAnnotation(tr.WINDOW):
                    win, items = _window(system, traffic, seed, seconds,
                                         gclock)
            obs.disable()
            spans = obs.events()
            reduced = tr.reduce_file(tr.find_xplane(log_dir))
        else:
            win, items = _window(system, traffic, seed, seconds, gclock)
            spans, reduced = [], None
    finally:
        obs.disable()
        gclock.close()
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    window_compiles = clock.count - setup_compiles
    stats = jax.devices()[0].memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    # a sampled request without an answer is among the unanswered ones
    answered = [it for it in items if it[-1] is not None]
    t_check = time.perf_counter()
    checked, checks = system.check(answered)
    check_s = time.perf_counter() - t_check
    unanswered = win["attempted"] - win["completed"]
    checks["answers_unanswered"] = (unanswered, 0)
    correct = checked > 0 and all(v <= lim for v, lim in checks.values())
    engine_failed = (win["engine"] or {}).get("failed", 0)
    lat = win.get("latencies_s")
    print(f"window: requests={win['attempted']} completed={win['completed']} "
          f"failed={engine_failed} wall_s={win['wall_s']:.6f} "
          f"setup_s={setup_s:.3f} setup_compiles={setup_compiles} "
          f"setup_compile_s={setup_compile_s:.3f} "
          f"window_compiles={window_compiles} "
          f"window_gc_passes={win['gc'][0]} window_gc_s={win['gc'][1]:.6f} "
          f"checked={checked} "
          f"check_s={check_s:.3f}"
          + (f" p50_ms={arith.percentile(lat, 50) * 1e3:.3f}"
             f" p95_ms={arith.percentile(lat, 95) * 1e3:.3f}"
             f" max_ms={max(lat) * 1e3:.3f}" if lat else "")
          + (f" drain_gaps={json.dumps(win['gaps'])}" if "gaps" in win
             else ""),
          file=sys.stderr)
    result = {"correct": correct, "attempted": int(win["attempted"]),
              "failed": int(unanswered), "metrics": {}, "device": {}}
    if device is not None:
        result["device"] = dict(device, memory_peak_bytes=mem_peak)
    if trace:
        observed = {"cell": cell["name"], "config": config,
                    "traffic": traffic, "window": win, "spans": spans,
                    "trace": reduced,
                    "peaks": (spec.load_peaks(bench_dir).get(device["kind"])
                              if device else None)}
        for m in spec.cell_metrics(bench, cell["name"], trace=True):
            value = spec.metric_reader(m["name"], bench_dir)(observed)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if device is not None:
            result["device"]["busy_s"] = reduced["busy_s"]
            result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["ops"],
                               "idle_gaps": reduced["gaps"]}
    else:
        for m in spec.cell_metrics(bench, cell["name"], trace=False):
            result["metrics"][m["name"]] = {
                "value": end_to_end(m["name"], win, setup_s), "unit": m["unit"]}
    if control:
        _, ctl = system.check(answered, control=True)
        result["control_correct"] = all(v <= lim for v, lim in ctl.values())
        result["control_checks"] = {k: {"value": v, "limit": lim}
                                    for k, (v, lim) in ctl.items()}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = spec.load_benchmark()
        cell = spec.find_workload(bench, args.workload)
        peaks = spec.load_peaks()
        enable_compile_cache()
        device = device_info(int(cell["chips"]), peaks)
    except (spec.SpecError, DeviceError) as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    result = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace),
                      device=device)
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']} limit={c['limit']}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
