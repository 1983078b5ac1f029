"""Trace reduction: busy union, idle share, top ops and idle gaps
attributed to host spans, on a synthetic profile and on a small trace
recorded on a TPU v5e by ``record_trace.py``."""
import gzip
import os
from types import SimpleNamespace as NS

import pytest

from harness import trace as tr

SMALL_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "small_trace.xplane.pb.gz")


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=e, start_ns=s, duration_ns=d)
                            for e, s, d in evs])
        for ln, evs in lines.items()])


def test_union_clipping_and_gap_attribution():
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [("fusion.1", 100, 300), ("fusion.2", 200, 300),
                    ("fusion.1", 800, 100), ("outside", 5000, 10)],
        "XLA Modules": [("jit_multiply", 100, 800)]})
    host = _plane("/host:CPU", {"python": [
        ("bench.window", 0, 1000), ("serve.dispatch", 500, 250),
        ("serve.run", 450, 500), ("unrelated", 0, 1000)]})
    out = tr.reduce_profile(NS(planes=[host, dev]))
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 500) and [800, 900) -> 500 ns of the 1000 ns window
    assert out["busy_s"] == pytest.approx(500e-9)
    assert dict(out["ops"]) == pytest.approx({"fusion.1": 400e-9,
                                              "fusion.2": 300e-9})
    # gaps: [500, 800) 300 ns in serve.dispatch, [0, 100) and [900, 1000)
    assert out["gaps"][0] == ["serve.dispatch", pytest.approx(300e-9)]
    assert sorted(g[1] for g in out["gaps"]) == pytest.approx(
        [100e-9, 100e-9, 300e-9])


def test_small_tpu_trace():
    """Four batched multiplies inside ``bench.window``, with a 50 ms host
    sleep in ``bench.sleep`` while nothing is queued on the device."""
    from jax.profiler import ProfileData

    with gzip.open(SMALL_TRACE, "rb") as f:
        out = tr.reduce_profile(ProfileData.from_serialized_xspace(f.read()))
    assert out["devices"] == 1
    assert 0.05 < out["window_s"] < 5.0
    assert 0.0 < out["busy_s"] < out["window_s"] - 0.045
    assert out["ops"] and all(t > 0 for _, t in out["ops"])
    longest = out["gaps"][0]
    assert longest[0] == "bench.sleep"
    assert 0.045 < longest[1] < 0.2
