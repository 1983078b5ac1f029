"""Process-wide metrics registry: counters and gauges.

The registry *backs* the serve / EvalPlan ``stats`` dicts rather than
replacing them: the dicts keep their exact keys and values (dozens of
tests pin them bit-for-bit), and the instrumented layers mirror the
same increments here as counters (``serve.*``, ``plan.*``,
``autotune.resolve.*``); the autotuner records its candidate evidence
as gauges.  ``write_metrics`` writes the snapshot beside a Perfetto
trace.

Like the tracer, everything is gated on ``obs.enabled()`` — a disabled
registry call is one flag check and a return, so mirroring can live
permanently on the hot paths.

All operations take one coarse lock; these are per-drain counters, not
per-sample nanosecond counters, so contention is nil.
"""
from __future__ import annotations

import threading
from collections import deque

from repro.obs import trace as _trace

# gauge sample history per gauge (timestamped, bounded)
MAX_GAUGE_SAMPLES = 4096

_LOCK = threading.Lock()
_COUNTERS: dict[str, float] = {}
_GAUGES: dict[str, dict] = {}       # name -> {"value": v, "samples": deque}


def counter_add(name: str, n: float = 1) -> None:
    if not _trace._ENABLED:
        return
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + n


def gauge_set(name: str, value: float) -> None:
    """Set a gauge and append a (ts_us, value) sample (bounded)."""
    if not _trace._ENABLED:
        return
    import time
    ts_us = (time.perf_counter_ns() - _trace._EPOCH_NS) / 1e3
    with _LOCK:
        g = _GAUGES.get(name)
        if g is None:
            g = _GAUGES[name] = {
                "value": value,
                "samples": deque(maxlen=MAX_GAUGE_SAMPLES)}
        g["value"] = value
        g["samples"].append((ts_us, value))


def snapshot() -> dict:
    """JSON-ready copy of the whole registry (the metrics artifact
    ``benchmarks/run.py --trace-out`` writes next to the trace)."""
    with _LOCK:
        counters = dict(_COUNTERS)
        gauges = {
            name: {"value": g["value"],
                   "samples": [list(s) for s in g["samples"]]}
            for name, g in _GAUGES.items()
        }
    return {"counters": counters, "gauges": gauges}


def reset() -> None:
    """Drop all metrics (tests / fresh capture)."""
    with _LOCK:
        _COUNTERS.clear()
        _GAUGES.clear()
