"""Drives ``CkksServeEngine.run_async`` for the open-loop and backlog
cells, and takes each open-loop request's completion time on the
harness's own clock.

The engine exposes no per-request completion time, so the harness
subclasses it: after the engine hands a drained group over
(``_drain``), the subclass waits for the group's device buffers itself
and stamps every request of the group with ``time.perf_counter()``.
Latency is that stamp minus the request's due time (window start plus
its arrival offset).
"""
from __future__ import annotations

import time

import jax

from repro.fhe.serve import CkksServeEngine


class TimedEngine(CkksServeEngine):
    """The engine under test, with the harness's completion stamps."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        if not callable(getattr(CkksServeEngine, "_drain", None)):
            raise RuntimeError("CkksServeEngine has no _drain hook to time")
        self.delivered: dict[int, float] = {}
        self.drains: list[tuple[float, str, int]] = []

    def _drain(self, batch, out, done_t, t0, stats):
        super()._drain(batch, out, done_t, t0, stats)
        kind, reqs, outs = batch
        jax.block_until_ready([x for ct in outs if hasattr(ct, "c0")
                               for x in (ct.c0.data, ct.c1.data)])
        now = time.perf_counter()
        for req in reqs:
            self.delivered[req.rid] = now
        self.drains.append((now, kind, len(reqs)))


def longest_gaps(drains: list, t0: float, count: int = 3) -> list:
    """The ``count`` longest times between consecutive drains, each as
    (seconds into the window, gap seconds, kind and size of the group
    drained at its end): where the serve loop stood still."""
    gaps = [(round(b[0] - t0, 3), round(b[0] - a[0], 4), b[1], b[2])
            for a, b in zip(drains, drains[1:])]
    return sorted(gaps, key=lambda g: -g[1])[:count]


_COUNTERS = ("dispatches", "batched_ops", "padded", "key_switches")


def _add_stats(total: dict, stats: dict) -> None:
    for c in _COUNTERS:
        total[c] = total.get(c, 0) + stats.get(c, 0)
    total["failed"] = total.get("failed", 0) + len(stats.get("failed", {}))


def run_backlog(engine: TimedEngine, next_chunk, seconds: float):
    """A backlog window: ``next_chunk()`` makes a chunk of requests, all
    due at once, which ``run_async`` drains before the next is made,
    until ``seconds`` have passed; the window closes when the last
    chunk's answers are all in.  Returns (window dict, every answer
    rid -> output)."""
    answers: dict = {}
    attempted = 0
    totals: dict = {}
    engine.drains = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        reqs = next_chunk()
        answers.update(engine.run_async(reqs))
        attempted += len(reqs)
        _add_stats(totals, engine.stats)
    t_end = time.perf_counter()
    window = {"t0": t0, "t_end": t_end, "last_drain": t_end,
              "wall_s": t_end - t0, "latencies_s": None,
              "attempted": attempted, "completed": len(answers),
              "engine": totals, "gaps": longest_gaps(engine.drains, t0)}
    return window, answers


def run_open(engine: TimedEngine, reqs: list, arrivals, keep: set):
    """One open-loop window: every request with its due time.  Returns
    (window dict, kept answers rid -> output)."""
    engine.delivered, engine.drains = {}, []
    t0 = time.perf_counter()
    out = engine.run_async(reqs, list(map(float, arrivals)))
    t_end = time.perf_counter()
    lat = []
    for req, a in zip(reqs, arrivals):
        done = engine.delivered.get(req.rid)
        lat.append(float("inf") if done is None or req.rid not in out
                   else done - (t0 + float(a)))
    last = max(engine.delivered.values(), default=t_end)
    totals: dict = {}
    _add_stats(totals, engine.stats)
    window = {"t0": t0, "t_end": t_end, "last_drain": last,
              "wall_s": last - t0, "latencies_s": lat,
              "attempted": len(reqs),
              "completed": sum(1 for r in reqs if r.rid in out),
              "engine": totals,
              "gaps": longest_gaps(engine.drains, t0)}
    return window, {rid: out[rid] for rid in keep if rid in out}
