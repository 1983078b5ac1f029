"""Metric arithmetic: percentiles, rates, spreads, and the key-switch
operation and byte counts at the benchmark's shapes."""
import math

import numpy as np
import pytest

from harness import arith


def test_percentile_of_known_list():
    v = list(range(1, 101))                       # 1..100
    assert arith.percentile(v, 50) == pytest.approx(50.5)
    assert arith.percentile(v, 99) == pytest.approx(99.01)
    assert arith.percentile(v, 100) == 100
    assert arith.percentile(v, 99) == pytest.approx(np.percentile(v, 99))


def test_percentile_counts_unanswered_as_infinite():
    v = [1.0] * 98 + [math.inf] * 2
    assert math.isinf(arith.percentile(v, 99))
    assert arith.percentile([1.0] * 99 + [math.inf], 50) == 1.0


def test_window_latency_metrics():
    import run
    from harness import spec

    win = {"latencies_s": [i / 1000 for i in range(1, 101)]}   # 1..100 ms
    assert run.end_to_end("p50_ms", win, 0.0) == pytest.approx(50.5)
    p99 = spec.metric_reader("serve.p99_ms")
    assert p99({"window": win}) == pytest.approx(99.01)
    assert p99({"window": {"latencies_s": None}}) is None


def test_rate_and_spread():
    assert arith.rate(300, 1.5) == 200.0
    with pytest.raises(ValueError):
        arith.rate(1, 0.0)
    # statistics.quantiles (exclusive) of 1..7: q1=2, q2=4, q3=6
    assert arith.spread([1, 2, 3, 4, 5, 6, 7]) == pytest.approx(1.0)


def test_keyswitch_counts_at_the_cell_shape():
    n, k = 1 << 14, 8
    assert arith.ntt_butterflies(n) == 8192 * 14
    # 98 residue polynomials transformed, 160 pointwise products of n
    assert arith.keyswitch_mulmods(n, k) == 98 * 114688 + 160 * n == 13_860_864
    # B=32 multiplies: 6 (k, n) u32 halves each plus one (k, k+1, n) key pair
    assert arith.relin_multiply_bytes(n, k, 32) == (
        6 * k * n * 4 * 32 + 2 * k * (k + 1) * n * 4) == 110_100_480
