"""Nestable, thread-safe span tracer on monotonic clocks.

``span("serve.dispatch", kind=..., n=...)`` is a context manager that
records a (name, start, duration, thread, depth, args) event into a
bounded global buffer; ``record(name, start_s, end_s, ...)`` records
one back-dated event whose start and end the caller took earlier on the
same clock (``time.perf_counter``).  ``obs.export`` renders the buffer
as Chrome trace-event JSON (Perfetto-loadable); the benchmark
(``bench/metrics``) reads it through ``events()``.

Design constraints, in order:

  * **One flag check when disabled (the default).**  ``span()`` checks
    one module-level flag and returns a shared no-op singleton — no
    object allocation, no clock read, no lock; ``record()`` returns at
    the same check.  So the probes live on the hot paths permanently.
  * **Thread-safe nesting.**  Each thread keeps its own span stack
    (depth makes the Perfetto rows nest); the event buffer is a
    ``deque(maxlen=...)`` appended under a lock only at span EXIT, so
    concurrent drains from worker threads interleave safely and an
    unbounded run can never exhaust memory (oldest events drop first).
  * **Exception safety.**  A span records its event in ``__exit__``
    unconditionally and never swallows the exception; a failed dispatch
    still shows up on the timeline (with ``error=`` in its args).
  * **Monotonic clocks.**  ``time.perf_counter_ns`` throughout;
    timestamps are microseconds relative to the module's load epoch
    (Chrome trace-event ``ts``/``dur`` are µs).
  * **Dependency-free, jax-optional.**  ``enable(forward_to_jax=True)``
    resolves ``jax.profiler.TraceAnnotation`` once; every span then also
    enters one, so host spans land in an active profiler session beside
    the device ops.  Each event says whether it was forwarded
    (``forwarded``): those are the spans that both clocks saw, from which
    ``bench/harness/clock.py`` puts the buffer on the profile's clock.
    Back-dated events are never forwarded.
"""
from __future__ import annotations

import threading
import time
from collections import deque

# bounded: a heavy-traffic soak must never OOM the host through its own
# instrument; 262144 events is ~30 MB and hours of serve phases
MAX_EVENTS = 262_144

_ENABLED = False
_ANNOTATION = None      # jax.profiler.TraceAnnotation while forwarding
_EVENTS: deque = deque(maxlen=MAX_EVENTS)
_LOCK = threading.Lock()
_TLS = threading.local()
_EPOCH_NS = time.perf_counter_ns()      # trace time zero (µs offsets)
_DROPPED = 0                            # events lost to the maxlen bound


def enabled() -> bool:
    return _ENABLED


def enable(*, forward_to_jax: bool = False) -> None:
    """Turn span recording on process-wide.  ``forward_to_jax=True``
    additionally wraps every span in ``jax.profiler.TraceAnnotation``
    (resolved here, once) so host spans show up inside an active XLA
    device profile; without jax the spans are recorded unforwarded."""
    global _ENABLED, _ANNOTATION
    _ANNOTATION = None
    if forward_to_jax:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except ImportError:         # jax absent / profiler API moved
            pass
    _ENABLED = True


def disable() -> None:
    global _ENABLED, _ANNOTATION
    _ENABLED = False
    _ANNOTATION = None


def clear() -> None:
    """Drop all recorded events (tests / fresh capture)."""
    global _DROPPED
    with _LOCK:
        _EVENTS.clear()
        _DROPPED = 0


def events() -> list[dict]:
    """Snapshot the event buffer as a list of plain dicts (oldest
    first): name, cat, ts_us, dur_us, tid, depth, args, forwarded (the
    span also entered a profiler ``TraceAnnotation``)."""
    with _LOCK:
        return [
            {"name": name, "cat": cat, "ts_us": ts, "dur_us": dur,
             "tid": tid, "depth": depth, "args": args, "forwarded": fwd}
            for (name, cat, ts, dur, tid, depth, args, fwd) in _EVENTS
        ]


def dropped() -> int:
    """Events lost to the ``MAX_EVENTS`` bound since the last clear."""
    return _DROPPED


def _stack() -> list:
    s = getattr(_TLS, "stack", None)
    if s is None:
        s = _TLS.stack = []
    return s


class _NoopSpan:
    """The shared disabled-path context manager: one module-level
    instance, so ``span(...)`` allocates NOTHING when tracing is off
    (pinned in tests/test_obs.py)."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def annotate(self, **args):
        pass


NOOP_SPAN = _NoopSpan()


def _append(name, cat, ts_us, dur_us, depth, args, forwarded) -> None:
    global _DROPPED
    with _LOCK:
        if len(_EVENTS) == MAX_EVENTS:
            _DROPPED += 1
        _EVENTS.append((name, cat, ts_us, dur_us, threading.get_ident(),
                        depth, args, forwarded))


class _Span:
    __slots__ = ("name", "cat", "args", "t0", "depth", "_jax_ctx")

    def __init__(self, name: str, cat: str, args: dict):
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = 0
        self.depth = 0
        self._jax_ctx = None

    def __enter__(self):
        stack = _stack()
        self.depth = len(stack)
        stack.append(self)
        if _ANNOTATION is not None:
            self._jax_ctx = _ANNOTATION(self.name)
            self._jax_ctx.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        if self._jax_ctx is not None:
            self._jax_ctx.__exit__(exc_type, exc, tb)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        if exc_type is not None:
            self.args = dict(self.args, error=exc_type.__name__)
        _append(self.name, self.cat, (self.t0 - _EPOCH_NS) / 1e3,
                (t1 - self.t0) / 1e3, self.depth, self.args,
                self._jax_ctx is not None)
        return False                # never swallow the exception

    def annotate(self, **args):
        """Add args learned inside the span (its event carries them)."""
        self.args = dict(self.args, **args)


def span(name: str, cat: str = "repro", **args):
    """Context manager timing one named phase.  Keyword args become the
    Perfetto event's ``args`` payload (keep them JSON-serializable).
    Returns the shared no-op singleton when tracing is disabled."""
    if not _ENABLED:
        return NOOP_SPAN
    return _Span(name, cat, args)


def record(name: str, start_s: float, end_s: float, cat: str = "repro",
           **args) -> None:
    """Record one complete event that started at ``start_s`` and ended
    at ``end_s``, both ``time.perf_counter()`` readings taken earlier:
    for phases that are only known to be over after the fact (a served
    request's lifecycle).  Nothing happens when tracing is disabled."""
    if not _ENABLED:
        return
    ts_us = (start_s * 1e9 - _EPOCH_NS) / 1e3
    _append(name, cat, ts_us, (end_s - start_s) * 1e6, len(_stack()), args,
            False)
