"""Reduction of a profiler trace to device busy time, idle share, top
device ops and idle gaps attributed to host spans.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData`` alone.  Device planes are named
``/device:TPU:<i>``; their ``XLA Ops`` line holds one event per
operation run.  The window is the host annotation ``bench.window`` that
the harness opens around its measured window.  Host spans of the
program (``serve.*``, ``plan.*``, ``ops.*``, ``mlkem.*``) reach the same
trace as ``TraceAnnotation`` events when ``repro.obs`` is enabled with
``forward_to_jax=True``.
"""
from __future__ import annotations

import contextlib
import glob
import os

WINDOW = "bench.window"
OP_LINE = "XLA Ops"
HOST_SPAN_PREFIXES = ("bench.", "serve.", "plan.", "ops.", "mlkem.")


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the enclosed block into ``log_dir`` (host spans and device
    ops; no Python function tracing)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(log_dir, profiler_options=opts):
        yield


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _events(plane, line_name=None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield ev.name, float(ev.start_ns), float(ev.duration_ns)


def reduce_profile(pd, top: int = 10) -> dict:
    """busy_s (union of device-op intervals inside the window, averaged
    over device planes), window_s, ops [(name, seconds)] by total device
    time, gaps [(host span, seconds)] for the longest idle stretches."""
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    host = [ev for p in pd.planes if p.name.startswith("/host:")
            for ev in _events(p)
            if ev[0].startswith(HOST_SPAN_PREFIXES)]
    wins = [(s, s + d) for name, s, d in host if name == WINDOW]
    dev_events = [[ev for ev in _events(p, OP_LINE)] for p in devices]
    if wins:
        w0, w1 = min(s for s, _ in wins), max(e for _, e in wins)
    else:
        flat = [ev for evs in dev_events for ev in evs]
        if not flat:
            return {"devices": len(devices), "busy_s": 0.0, "window_s": 0.0,
                    "ops": [], "gaps": []}
        w0 = min(s for _, s, _ in flat)
        w1 = max(s + d for _, s, d in flat)
    ops: dict = {}
    busy_total = 0.0
    gaps = []
    spans = [(s, s + d, name) for name, s, d in host if name != WINDOW]
    for evs in dev_events:
        clipped = []
        for name, s, d in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                ops[name] = ops.get(name, 0.0) + (b - a)
        merged = _union(clipped)
        busy_total += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    n_dev = max(1, len(devices))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_host_span_at(spans, (a + b) / 2), (b - a) / 1e9]
             for a, b in gaps[:top]]
    return {
        "devices": len(devices),
        "busy_s": busy_total / n_dev / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "ops": [[name, t / n_dev / 1e9] for name, t in
                sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "gaps": named,
    }


def _host_span_at(spans, t: float) -> str:
    """The innermost host span open at time t (``host.idle`` if none)."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "host.idle"


def reduce_file(path: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), top)
