"""The observability layer (``repro.obs``): span tracer semantics,
back-dated events, forwarding to the profiler, exporter golden format,
the disabled fast path, and the serve-stats compatibility contract the
registry mirrors (never replaces).

The key invariants:

  * spans nest per thread (depth), survive exceptions (the event is
    recorded WITH an error tag and the exception propagates), and
    interleave safely across threads;
  * ``record`` lays a back-dated event on the spans' clock, and every
    event says whether it also reached the profiler (``forwarded``);
  * the Chrome trace export round-trips through ``json.loads`` with the
    ``ph``/``ts``/``dur``/``name`` fields Perfetto requires, and a
    nested span's interval is contained in its parent's;
  * disabled (the default), ``span()`` returns one shared no-op
    singleton — no allocation, no events, no metrics;
  * the serve engine's ``stats`` dict keeps its full key contract with
    obs enabled, BOTH drains report ``latency_us`` (empty-but-present
    on a zero-request drain — the sync-parity fix), and the autotuner
    records its measurement evidence.
"""
import json
import threading

import pytest

from repro import obs
from repro.obs import trace as obs_trace


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts disabled with empty buffers and leaves the
    process the same way (obs state is module-global)."""
    obs.disable()
    obs.clear()
    obs.reset()
    yield
    obs.disable()
    obs.clear()
    obs.reset()


# ------------------------------------------------------------ span tracer

def test_disabled_span_is_shared_noop_singleton():
    # the zero-allocation fast path: every disabled call returns the
    # SAME module-level object and records nothing
    s1 = obs.span("a", kind="x")
    s2 = obs.span("b")
    assert s1 is s2 is obs.NOOP_SPAN
    with s1:
        pass
    assert obs.events() == []


def test_disabled_metrics_record_nothing():
    obs.counter_add("c", 5)
    obs.gauge_set("g", 1.0)
    obs.record("h", 0.0, 2.0)
    snap = obs.snapshot()
    assert snap == {"counters": {}, "gauges": {}}
    assert obs.events() == []


def test_span_nesting_depth_and_order():
    obs.enable()
    with obs.span("outer"):
        with obs.span("mid"):
            with obs.span("inner"):
                pass
    evs = obs.events()
    # innermost exits first
    assert [e["name"] for e in evs] == ["inner", "mid", "outer"]
    assert [e["depth"] for e in evs] == [2, 1, 0]


def test_span_exception_safety():
    obs.enable()
    with pytest.raises(ValueError, match="boom"):
        with obs.span("failing", kind="dispatch"):
            raise ValueError("boom")
    (ev,) = obs.events()
    assert ev["name"] == "failing"
    assert ev["args"]["error"] == "ValueError"
    assert ev["args"]["kind"] == "dispatch"
    assert ev["dur_us"] >= 0.0
    # the span popped its own stack frame despite the exception
    with obs.span("after"):
        pass
    assert obs.events()[-1]["depth"] == 0


def test_span_thread_safety():
    obs.enable()
    n_threads, n_spans = 8, 50
    barrier = threading.Barrier(n_threads)

    def work(tid):
        barrier.wait()
        for i in range(n_spans):
            with obs.span("t", tid=tid):
                with obs.span("t.in", tid=tid):
                    pass

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = obs.events()
    assert len(evs) == n_threads * n_spans * 2
    # per-thread nesting never leaked across threads: every inner span
    # has depth 1, every outer depth 0, on every thread
    for e in evs:
        assert e["depth"] == (1 if e["name"] == "t.in" else 0)


def test_event_buffer_is_bounded():
    obs.enable()
    old_max, obs_trace.MAX_EVENTS = obs_trace.MAX_EVENTS, 16
    # the deque bound is fixed at construction; rebuild a tiny one
    old_events = obs_trace._EVENTS
    obs_trace._EVENTS = type(old_events)(maxlen=16)
    try:
        for i in range(40):
            with obs.span(f"s{i}"):
                pass
        assert len(obs.events()) == 16
        assert obs.dropped() == 24
        # oldest dropped first
        assert obs.events()[0]["name"] == "s24"
    finally:
        obs_trace.MAX_EVENTS = old_max
        obs_trace._EVENTS = old_events


# ------------------------------------------ back-dated events, forwarding

def test_record_lands_on_the_span_clock():
    import time
    obs.enable()
    t0 = time.perf_counter()
    with obs.span("live"):
        pass
    t1 = t0 + 0.0125
    obs.record("late", t0, t1, rid=7)
    live, late = obs.events()
    assert late["name"] == "late" and late["args"] == {"rid": 7}
    assert late["dur_us"] == pytest.approx(12_500.0, abs=1e-3)
    # same clock: the span opened after t0, within a millisecond
    assert 0.0 <= live["ts_us"] - late["ts_us"] < 1e3
    assert late["forwarded"] is False and late["depth"] == 0


def test_record_inside_a_span_takes_its_depth():
    import time
    obs.enable()
    with obs.span("outer"):
        t = time.perf_counter()
        obs.record("inner", t, t)
    inner, outer = obs.events()
    assert (inner["depth"], outer["depth"]) == (1, 0)
    assert inner["dur_us"] == 0.0


def test_annotate_adds_args_and_is_noop_when_disabled():
    with obs.span("off") as sp:
        sp.annotate(x=1)            # the shared singleton ignores it
    assert obs.events() == []
    obs.enable()
    with obs.span("on", a=1) as sp:
        sp.annotate(b=2)
    (ev,) = obs.events()
    assert ev["args"] == {"a": 1, "b": 2}


def test_forwarded_flag_follows_enable():
    obs.enable()
    with obs.span("plain"):
        pass
    obs.enable(forward_to_jax=True)
    with obs.span("fwd"):
        pass
    plain, fwd = obs.events()
    assert plain["forwarded"] is False
    assert fwd["forwarded"] is True


def test_enable_resolves_trace_annotation_once(monkeypatch):
    import jax.profiler

    made = []

    class Fake:
        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Fake)
    obs.enable(forward_to_jax=True)
    # a later change to the attribute is not looked up again per span
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", None)
    with obs.span("a"):
        with obs.span("b"):
            pass
    assert made == ["a", "b"]
    obs.disable()
    assert obs_trace._ANNOTATION is None


def test_gauge_samples_are_timestamped_and_bounded():
    obs.enable()
    for depth in (3, 1, 4, 1, 5):
        obs.gauge_set("queue", depth)
    g = obs.snapshot()["gauges"]["queue"]
    assert g["value"] == 5
    assert [v for _, v in g["samples"]] == [3, 1, 4, 1, 5]
    ts = [t for t, _ in g["samples"]]
    assert ts == sorted(ts)


def test_counters_accumulate():
    obs.enable()
    obs.counter_add("c")
    obs.counter_add("c", 4)
    assert obs.snapshot()["counters"]["c"] == 5


# -------------------------------------------------------------- exporters

def test_chrome_trace_golden_format():
    obs.enable()
    with obs.span("parent", kind="dispatch"):
        with obs.span("child"):
            pass
    blob = json.dumps(obs.chrome_trace())
    back = json.loads(blob)            # the Perfetto round-trip
    evs = back["traceEvents"]
    assert len(evs) == 2
    for e in evs:
        for field in ("name", "cat", "ph", "ts", "dur", "pid", "tid"):
            assert field in e, f"trace event missing {field!r}"
        assert e["ph"] == "X"
        assert e["dur"] >= 0.0
    child = next(e for e in evs if e["name"] == "child")
    parent = next(e for e in evs if e["name"] == "parent")
    # nesting shows as interval containment on the same track
    assert parent["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e-6
    assert child["args"]["depth"] == 1
    assert parent["args"]["kind"] == "dispatch"


def test_write_trace_and_metrics(tmp_path):
    obs.enable()
    with obs.span("x"):
        pass
    obs.counter_add("n", 2)
    tp, mp = tmp_path / "t.json", tmp_path / "m.json"
    obs.write_trace(str(tp))
    obs.write_metrics(str(mp))
    with open(tp) as f:
        t = json.load(f)
    with open(mp) as f:
        m = json.load(f)
    assert t["traceEvents"][0]["name"] == "x"
    assert m["counters"]["n"] == 2


# ------------------------------------------- serve-stats compatibility

def _ctx():
    from repro.fhe.ckks import CkksContext
    return CkksContext(n=256, levels=2, scale_bits=26, seed=71)


SERVE_STAT_KEYS = {
    "mode", "dispatches", "batched_ops", "padded", "identity", "failed",
    "groups", "devices", "per_device_rows", "program_dispatches",
    "key_switches", "decomposes", "hoisted_reuse", "fresh_traces",
    "wall_s", "latency_us",
}


def test_serve_stats_contract_with_obs_enabled():
    """The full stats contract holds with instrumentation ON, both
    drains report latency_us (sync parity — S1), the answers stay
    bit-exact vs the uninstrumented drain, and the phase spans land."""
    from conftest import ct_equal
    from repro.fhe.serve import CkksServeEngine, synthetic_trace

    ctx = _ctx()
    reqs, _ = synthetic_trace(ctx, 12, seed=5)
    engine = CkksServeEngine(ctx.plan(), batch_tile=2)

    baseline = engine.run(list(reqs))          # obs disabled
    base_keys = dict(engine.stats)
    obs.enable()
    out_sync = engine.run(list(reqs))
    sync_stats = dict(engine.stats)
    out_async = engine.run_async(list(reqs))
    async_stats = dict(engine.stats)
    obs.disable()

    for stats in (sync_stats, async_stats):
        assert SERVE_STAT_KEYS <= set(stats)
        lat = stats["latency_us"]
        assert set(lat) == {"p50", "p99", "mean", "max", "count"}
        assert lat["count"] == len(reqs)
        assert 0 < lat["p50"] <= lat["p99"] <= lat["max"]
    assert "max_queue" in async_stats
    # pre-existing keys unchanged in value vs the uninstrumented drain
    for key in ("mode", "dispatches", "batched_ops", "padded", "identity",
                "program_dispatches", "key_switches", "decomposes",
                "hoisted_reuse", "groups"):
        assert sync_stats[key] == base_keys[key], key
    for rid, ct in baseline.items():
        assert ct_equal(ct, out_sync[rid]) and ct_equal(ct, out_async[rid])
    # every serve phase shows up as at least one span
    names = {e["name"] for e in obs.events()}
    for phase in ("serve.run", "serve.screen", "serve.group",
                  "serve.dispatch", "serve.block", "plan.stack",
                  "plan.program"):
        assert phase in names, f"no span for {phase}"
    # the mirrored registry agrees with the dict on monotone counters
    counters = obs.snapshot()["counters"]
    assert counters["serve.batched_ops"] == (sync_stats["batched_ops"]
                                             + async_stats["batched_ops"])
    assert counters["serve.drains"] == 2
    # the per-request record: one serve.request per request and drain,
    # each naming a batch that a serve.dispatch span carries
    evs = obs.events()
    reqs_evs = [e for e in evs if e["name"] == "serve.request"]
    assert sorted(e["args"]["rid"] for e in reqs_evs) == sorted(
        [r.rid for r in reqs] * 2)
    batches = {e["args"]["batch"] for e in evs
               if e["name"] == "serve.dispatch"}
    assert len(batches) == (sync_stats["dispatches"]
                            + async_stats["dispatches"])
    assert {e["args"]["batch"] for e in reqs_evs} - {None} <= batches


def test_zero_request_drains_report_empty_latency():
    """S1: both drains emit an empty-but-present latency_us on empty
    input, so consumers indexing it never KeyError."""
    from repro.fhe.serve import CkksServeEngine

    engine = CkksServeEngine(_ctx().plan(), batch_tile=2)
    assert engine.run([]) == {}
    assert engine.stats["latency_us"] == {}
    assert engine.run_async([]) == {}
    assert engine.stats["latency_us"] == {}


def test_sync_latency_counts_failures_and_identity():
    """The sync drain's latency covers every resolved request —
    dispatched, identity-short-circuited, or failed — like run_async."""
    from repro.fhe.ckks import CkksContext
    from repro.fhe.serve import CkksServeEngine, FheRequest

    ctx = _ctx()
    plan = ctx.plan()
    ct = ctx.encrypt(ctx.encode([0.5] * ctx.slots))
    low = plan.rescale(plan.rescale(ct))       # exhausted: rescale fails
    reqs = [
        FheRequest(0, "rotate", ct, r=1),
        FheRequest(1, "rotate", ct, r=0),      # identity short-circuit
        FheRequest(2, "rescale", low),         # screened out: level
    ]
    engine = CkksServeEngine(plan, batch_tile=2)
    out = engine.run(reqs)
    assert set(out) == {0, 1}
    assert engine.stats["identity"] == 1
    assert list(engine.stats["failed"]) == [2]
    assert engine.stats["latency_us"]["count"] == 3


# --------------------------------------------------- autotune evidence

def test_autotune_measure_records_evidence(monkeypatch):
    from repro.kernels import autotune

    monkeypatch.delenv(autotune.ENV_PIN, raising=False)
    monkeypatch.delenv(autotune.ENV_CACHE, raising=False)
    autotune.clear()
    got = autotune.measure("ntt", 1, 64, 32, reps=1)
    key = (autotune._backend(), "ntt", 1, 64, 32, "uint32")
    ev = autotune._EVIDENCE[key]
    assert ev["chosen"] == got
    assert ev["source"] == "measured"
    # every legal candidate tile (multiple of 8, <= b) carries a
    # median-seconds entry
    assert set(ev["candidates"]) == {8, 16, 32}
    assert all(s > 0 for s in ev["candidates"].values())
    tab = autotune.table()
    ks = "|".join(str(p) for p in key)
    assert tab["evidence"][ks]["chosen"] == got
    assert tab["evidence"][ks]["candidates"] == {
        str(t): s for t, s in ev["candidates"].items()}
    autotune.clear()


def test_autotune_evidence_roundtrips_through_sidecar(tmp_path, monkeypatch):
    from repro.kernels import autotune

    monkeypatch.delenv(autotune.ENV_PIN, raising=False)
    autotune.clear()
    autotune.measure("ntt", 1, 64, 2, reps=1)
    path = tmp_path / "cache.json"
    autotune.dump(str(path))
    autotune.clear()
    monkeypatch.setenv(autotune.ENV_CACHE, str(path))
    autotune._DISK_LOADED = False
    assert autotune.resolve_tile("ntt", 1, 64, 2) > 0    # seeds from disk
    key = (autotune._backend(), "ntt", 1, 64, 2, "uint32")
    ev = autotune._EVIDENCE[key]
    # provenance survives: the entry is marked disk-seeded but keeps the
    # measured candidate table from the sidecar
    assert ev["source"] == "disk"
    assert ev["candidates"] and all(
        isinstance(t, int) and s > 0 for t, s in ev["candidates"].items())
    autotune.clear()


def test_autotune_provenance_counters(monkeypatch):
    from repro.kernels import autotune

    monkeypatch.delenv(autotune.ENV_PIN, raising=False)
    monkeypatch.delenv(autotune.ENV_AUTOTUNE, raising=False)
    autotune.clear()
    obs.enable()
    autotune.resolve_tile("ntt", 1, 256, 8)               # miss -> default
    key = (autotune._backend(), "ntt", 1, 256, 8, "uint32")
    monkeypatch.setitem(autotune._MEM, key, 4)
    autotune.resolve_tile("ntt", 1, 256, 8)               # hit
    autotune.resolve_tile("ntt", 1, 256, 8, tile=2)       # explicit
    monkeypatch.setenv(autotune.ENV_PIN, "8")
    autotune.resolve_tile("ntt", 1, 256, 8)               # pin
    c = obs.snapshot()["counters"]
    assert c["autotune.resolve.cache_miss"] == 1
    assert c["autotune.resolve.default"] == 1
    assert c["autotune.resolve.cache_hit"] == 1
    assert c["autotune.resolve.explicit"] == 1
    assert c["autotune.resolve.pin"] == 1
    autotune.clear()
