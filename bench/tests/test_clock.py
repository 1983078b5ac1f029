"""``harness.clock``: the program's events on the profile's clock, from
a real CPU profile; the device's idle stretches and the program's host
spans read from a profile; the interval arithmetic; and the share of
``serve.run`` in which the device idled while work waited."""
import gzip
import json
import os
import time
from types import SimpleNamespace as NS

import pytest

from harness import clock, trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL_TRACE = os.path.join(DATA, "small_trace.xplane.pb.gz")
# what trace.reduce_profile gives for the small trace, to the bit
SMALL_REDUCED = os.path.join(DATA, "small_trace.reduced.json")


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=e, start_ns=s, duration_ns=d)
                            for e, s, d in evs])
        for ln, evs in lines.items()])


def test_forwarded_spans_land_on_their_profile_starts(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from repro import obs

    x = jnp.ones((128, 128), jnp.float32)
    (x @ x).block_until_ready()
    obs.clear()
    obs.enable(forward_to_jax=True)
    try:
        with tr.capture(str(tmp_path)):
            with jax.profiler.TraceAnnotation(tr.WINDOW):
                for i in range(6):
                    with obs.span("serve.dispatch", batch=i):
                        with obs.span("plan.program"):
                            (x @ x).block_until_ready()
                    time.sleep(0.003)
                t = time.perf_counter()
                obs.record("serve.request", t - 0.002, t)
    finally:
        obs.disable()
    events = obs.events()
    obs.clear()
    spans = clock.host_spans(
        ProfileData.from_file(tr.find_xplane(str(tmp_path))))
    off = clock.offset_ns(events, spans)
    assert off is not None
    fwd = [e for e in events if e["forwarded"]]
    assert len(fwd) == 12
    assert not any(e["forwarded"] for e in events
                   if e["name"] == "serve.request")
    for name in ("serve.dispatch", "plan.program"):
        prof = sorted(s for n, s, _ in spans if n == name)
        mine = sorted(e["ts_us"] for e in fwd if e["name"] == name)
        assert len(prof) == len(mine) == 6
        for p, m in zip(prof, mine):
            assert abs(clock.to_profile(m, off) - p) < 50e3   # 50 us


def test_offset_needs_equal_counts_and_forwarded_spans():
    ev = [{"name": "a", "ts_us": 1.0, "forwarded": True},
          {"name": "a", "ts_us": 5.0, "forwarded": True},
          {"name": "b", "ts_us": 2.0, "forwarded": True},
          {"name": "c", "ts_us": 3.0, "forwarded": False}]
    spans = [["a", 1000.0 + 7, 0], ["a", 5000.0 + 9, 0],   # a: 7, 9
             ["b", 1.0, 0], ["b", 2.0, 0],                  # b: 2 vs 1
             ["c", 3000.0 + 100, 0]]                        # not forwarded
    assert clock.offset_ns(ev, spans) == 8.0
    assert clock.offset_ns([{"name": "a", "ts_us": 1.0}], spans) is None
    assert clock.offset_ns(ev, []) is None


def test_interval_arithmetic():
    a = clock.union([[5, 7], [0, 2], [1, 3]])
    assert a == [[0, 3], [5, 7]]
    b = [[2, 6]]
    assert clock.intersect(a, b) == [[2, 3], [5, 6]]
    assert clock.length(clock.intersect(a, b)) == 2
    assert clock.intersect(a, []) == []
    assert clock.to_profile(2.5, 100.0) == pytest.approx(2600.0)


def test_idle_stretches_and_host_spans_of_a_profile():
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [("fusion.1", 100, 300), ("fusion.2", 200, 300),
                    ("fusion.1", 800, 100), ("outside", 5000, 10)],
        "XLA Modules": [("jit_multiply", 0, 1000)]})
    host = _plane("/host:CPU", {"python": [
        ("bench.window", 0, 1000), ("serve.dispatch", 500, 250),
        ("unrelated", 0, 1000)]})
    pd = NS(planes=[host, dev])
    assert clock.device_idle_ns(pd) == [[0, 100], [500, 800], [900, 1000]]
    assert clock.host_spans(pd) == [["serve.dispatch", 500, 750]]
    # the idle seconds are those of the reduction's busy share
    out = tr.reduce_profile(pd)
    idle = clock.length(clock.device_idle_ns(pd)) / 1e9
    assert idle == pytest.approx(out["window_s"] - out["busy_s"])


def test_idle_stretches_are_where_every_device_idles():
    devs = [_plane("/device:TPU:0", {"XLA Ops": [("a", 100, 400)]}),
            _plane("/device:TPU:1", {"XLA Ops": [("a", 300, 400)]})]
    host = _plane("/host:CPU", {"python": [("bench.window", 0, 1000)]})
    pd = NS(planes=[host, *devs])
    assert clock.device_idle_ns(pd) == [[0, 100], [700, 1000]]
    # never more than the idle seconds of the mean device
    out = tr.reduce_profile(pd)
    idle = clock.length(clock.device_idle_ns(pd)) / 1e9
    assert idle <= out["window_s"] - out["busy_s"]
    # no window, or no device: nothing to read
    assert clock.device_idle_ns(NS(planes=devs)) == []
    assert clock.device_idle_ns(NS(planes=[host])) == []


def test_small_tpu_trace():
    """On a trace recorded on a TPU v5e the idle stretches add up to the
    reduction's idle seconds, and the reduction itself reads as pinned."""
    from jax.profiler import ProfileData

    with gzip.open(SMALL_TRACE, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    out = tr.reduce_profile(pd)
    with open(SMALL_REDUCED) as f:
        assert out == json.load(f)
    idle = clock.length(clock.device_idle_ns(pd)) / 1e9
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-9)
    assert {n for n, _, _ in clock.host_spans(pd)} >= {"bench.sleep",
                                                       "plan.program"}


def _req(rid, due, adm, disp, ready, batch):
    """A serve.request event; times in ms on the obs clock."""
    return {"name": "serve.request", "ts_us": due * 1e3,
            "dur_us": (ready - due) * 1e3, "tid": 1, "depth": 1,
            "forwarded": False,
            "args": {"rid": rid, "kind": "rotate",
                     "admitted_us": (adm - due) * 1e3,
                     "dispatched_us": (disp - due) * 1e3, "batch": batch}}


def _span(name, start_ms, end_ms, **args):
    return {"name": name, "ts_us": start_ms * 1e3,
            "dur_us": (end_ms - start_ms) * 1e3, "tid": 1, "depth": 0,
            "forwarded": True, "args": args}


# due, admitted, dispatched, ready (ms)
REQS = [_req(0, 0.0, 0.5, 2.0, 10.0, 1),
        _req(1, 1.0, 1.0, 2.0, 10.0, 1),
        _req(2, 4.0, 6.0, 12.0, 20.0, 2),
        _req(3, 5.0, 5.0, 5.0, 5.0, None)]      # resolved at admission


def test_idle_with_work():
    """Profile clock = obs clock + 1 ms.  serve.run is [0, 20] ms (obs),
    waiting work is [0, 2] u [4, 12] (obs), the device idles over
    [1.5, 3.5] and [10, 16] ms on the profile's clock, i.e. [0.5, 2.5]
    and [9, 15] on the obs clock: held idle [0.5, 2] + [9, 12] = 4.5 ms
    of the 20 ms run."""
    events = REQS + [_span("serve.run", 0.0, 20.0),
                     _span("serve.dispatch", 2.0, 3.0, batch=1)]
    spans = [["serve.run", 1e6, 21e6], ["serve.dispatch", 3e6, 4e6]]
    idle = [[1.5e6, 3.5e6], [10e6, 16e6]]
    got = clock.idle_with_work_pct(events, idle, spans)
    assert got == pytest.approx(100.0 * 4.5 / 20.0)
    # never more than the device's idle
    assert got / 100.0 * 20e6 <= clock.length(idle)


def test_idle_with_work_silent_without_its_inputs():
    run = _span("serve.run", 0.0, 20.0)
    spans = [["serve.run", 1e6, 21e6]]
    assert clock.idle_with_work_pct([run], [[0, 1e6]], spans) is None
    assert clock.idle_with_work_pct(REQS, [[0, 1e6]], spans) is None
    assert clock.idle_with_work_pct(REQS + [run], [[0, 1e6]], []) is None
