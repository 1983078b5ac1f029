"""One timeline for the program's events and the profiler's.

``repro.obs`` stamps its events with ``time.perf_counter`` (microseconds
from its own epoch); the profiler stamps host and device events in
nanoseconds from its session's start.  The spans that ``obs`` forwarded
to ``jax.profiler.TraceAnnotation`` (``forwarded``) were seen by both, so
the offset between the clocks is read off them: the k-th forwarded span
of a name is the k-th host span of that name in the profile
(``host_spans``), and the offset is the median of their start
differences over every name that both saw equally often.  Back-dated
events (``serve.request``) then land on the profile's clock with the
same offset.

``host_spans`` and ``device_idle_ns`` read a ``jax.profiler.ProfileData``
the way ``trace.reduce_profile`` reads it (same planes, same window), and
``idle_with_work_pct`` lays the engine's waiting requests over the
device's idle stretches.
"""
from __future__ import annotations

import statistics
from collections import defaultdict

from harness.trace import (HOST_SPAN_PREFIXES, OP_LINE, WINDOW, _events,
                           _union as union)   # merged, sorted [start, end]


def host_spans(pd) -> list:
    """The program's host spans [[name, start, end]] (ns, the profile's
    clock), ``bench.window`` left out."""
    return [[name, s, s + d] for p in pd.planes if p.name.startswith("/host:")
            for name, s, d in _events(p)
            if name.startswith(HOST_SPAN_PREFIXES) and name != WINDOW]


def device_idle_ns(pd) -> list:
    """The merged stretches [[start, end]] of the ``bench.window`` span in
    which no device ran an op (ns, the profile's clock); [] without a
    device plane or a window."""
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    wins = [(s, s + d) for p in pd.planes if p.name.startswith("/host:")
            for name, s, d in _events(p) if name == WINDOW]
    if not devices or not wins:
        return []
    w0, w1 = min(s for s, _ in wins), max(e for _, e in wins)
    busy = union([[max(s, w0), min(s + d, w1)] for p in devices
                  for _, s, d in _events(p, OP_LINE)
                  if min(s + d, w1) > max(s, w0)])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    return [[a, b] for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def offset_ns(events: list, spans: list) -> float | None:
    """``profile_ns = ts_us * 1e3 + offset`` for ``obs.events()``, given
    the profile's ``host_spans``; None where no forwarded span can be
    matched."""
    seen = defaultdict(list)
    for ev in events:
        if ev.get("forwarded"):
            seen[ev["name"]].append(ev["ts_us"] * 1e3)
    prof = defaultdict(list)
    for name, start, _ in spans:
        if name in seen:
            prof[name].append(start)
    diffs = []
    for name, starts in seen.items():
        if len(prof[name]) == len(starts):
            diffs += [p - s for p, s in zip(sorted(prof[name]), sorted(starts))]
    return statistics.median(diffs) if diffs else None


def to_profile(ts_us: float, offset: float) -> float:
    """An ``obs`` time (us) on the profile's clock (ns)."""
    return ts_us * 1e3 + offset


def intersect(a: list, b: list) -> list:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def idle_with_work_pct(events: list, idle: list, spans: list) -> float | None:
    """Share of the ``serve.run`` interval in which the device was idle
    (``idle``, from ``device_idle_ns``) while some request was due and not
    yet dispatched: the union of [due, dispatched) over the engine's
    ``serve.request`` events.  Device idle the host caused, not idle from
    missing traffic; its seconds are part of the window's idle seconds.
    None without those events, a ``serve.run`` span or a matched clock."""
    from harness import lifecycle

    reqs = lifecycle.requests(events)
    runs = [e for e in events if e["name"] == "serve.run"]
    off = offset_ns(events, spans)
    if not reqs or not runs or off is None:
        return None
    run = union([[to_profile(e["ts_us"], off),
                  to_profile(e["ts_us"] + e["dur_us"], off)] for e in runs])
    waiting = union([[to_profile(r["due"], off),
                      to_profile(r["dispatched"], off)] for r in reqs])
    held = intersect(intersect(union(idle), waiting), run)
    return 100.0 * length(held) / length(run)
