"""Runtime observability for the kernels -> EvalPlan -> serve stack:
span tracing (``obs.trace``), a counter/gauge registry
(``obs.metrics``) and Perfetto/JSON exporters (``obs.export``).

One switch governs everything: ``obs.enable()`` / ``obs.disable()``.
Disabled (the default), every instrumentation point is a single flag
check — ``span()`` returns a shared no-op singleton, ``record()`` and
registry calls return immediately — so the hot paths carry their probes
permanently.

The probes, and who reads them:

  * phase spans ``serve.run`` / ``serve.screen`` / ``serve.group`` /
    ``serve.dispatch`` (``batch=`` id) / ``serve.block``,
    ``plan.program`` / ``plan.stack``, ``ops.*`` kernel calls and
    ``mlkem.*`` batches: the benchmark's trace reduction names device
    idle gaps by them, and ``mlkem.host_us_per_req`` reads the
    ``mlkem.encaps_batch`` spans;
  * ``serve.request``: one back-dated event per resolved request, from
    its due time to its answer being ready, with the admitted and
    dispatched offsets and the ``batch`` it rode
    (``CkksServeEngine``): read by ``serve.admit_lag_p99_ms``,
    ``serve.queue_ms`` and ``serve.inflight_ms``, and laid over a
    profile's device idle by the benchmark's clock module (device idle
    while requests wait);
  * ``plan.batch_key``: one span per stacked galois-key lookup
    (``hit``, ``b``, ``resident_bytes`` on a miss): read by
    ``plan.batch_key_miss_pct``;
  * counters mirroring the serve / plan ``stats`` dicts and the
    autotuner's resolutions, gauges of its candidate timings: for a
    Perfetto capture's metrics file.

``enable(forward_to_jax=True)`` also forwards each span to
``jax.profiler.TraceAnnotation`` and marks the event ``forwarded``, so a
profiler session sees the host spans beside the device ops.

Typical capture::

    from repro import obs
    obs.enable(); obs.clear(); obs.reset()
    engine.run_async(reqs, arrivals)
    obs.write_trace("drain_trace.json")      # -> ui.perfetto.dev
    obs.write_metrics("drain_metrics.json")  # counters/gauges
"""
from repro.obs.trace import (NOOP_SPAN, clear, disable, dropped, enable,
                             enabled, events, record, span)
from repro.obs.metrics import counter_add, gauge_set, reset, snapshot
from repro.obs.export import (chrome_trace, metrics_snapshot, write_metrics,
                              write_trace)

__all__ = [
    "NOOP_SPAN", "clear", "disable", "dropped", "enable", "enabled",
    "events", "record", "span",
    "counter_add", "gauge_set", "reset", "snapshot",
    "chrome_trace", "metrics_snapshot", "write_metrics", "write_trace",
]
