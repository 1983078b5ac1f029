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


def test_grouped_keyswitch_counts_by_hand():
    """n = 8 (12 butterflies), k = 3 primes in dnum = 2 digits of 2 and 1
    primes, alpha = 2 special primes."""
    n, k, dnum, alpha = 8, 3, 2, 2
    assert arith.digit_sizes(k, dnum) == [2, 1]
    # transforms: 3 digit inverses + 2 x 5 extension forwards
    # + 2 x (2 special inverses + 3 forwards) = 23
    # pointwise: mod-up of the 2-prime digit 2 x (1 + 5) = 12, key
    # products 2 x 2 x 5 = 20, per mod-down a conversion from 2 primes to
    # 3, 2 x (1 + 3) = 8, and 3 multiplications by P^-1: 2 x 11 = 22
    assert arith.keyswitch_mulmods(n, k, dnum, alpha) == \
        23 * 12 + (12 + 20 + 22) * 8 == 708
    # one multiply: 6 halves of (3, 8) u32, a key pair of (2, 5, 8) u32
    assert arith.relin_multiply_bytes(n, k, 1, dnum=dnum, alpha=alpha) == \
        6 * 3 * 8 * 4 + 2 * 2 * 5 * 8 * 4 == 1216


def test_counts_at_todays_shape_whether_named_or_not():
    for n, k in ((1 << 14, 8), (1 << 10, 3)):
        assert arith.keyswitch_mulmods(n, k, k, 1) == \
            arith.keyswitch_mulmods(n, k)
        assert arith.relin_multiply_bytes(n, k, 32, dnum=k, alpha=1) == \
            arith.relin_multiply_bytes(n, k, 32)


def test_kernel_readers_take_the_configured_shape():
    from harness import spec

    obs = {"window": {"calls": 10, "plan": {"key_switches": 320}},
           "trace": {"busy_s": 2.0}, "peaks": {"hbm_bytes_per_s": 819e9},
           "traffic": {"batch": 32},
           "config": {"n": 1 << 14, "levels": 7}}
    roof = spec.metric_reader("kernel.keyswitch_roofline")
    rate = spec.metric_reader("kernel.ks_mulmod_per_ns")
    assert roof(obs) == pytest.approx(100 * 10 * 110_100_480 / (2 * 819e9))
    assert rate(obs) == pytest.approx(320 * 13_860_864 / 2e9)
    obs["config"].update(n=1 << 15, levels=20, dnum=3, special_primes=7)
    assert roof(obs) == pytest.approx(100 * 10 * arith.relin_multiply_bytes(
        1 << 15, 21, 32, dnum=3, alpha=7) / (2 * 819e9))
    assert rate(obs) == pytest.approx(
        320 * arith.keyswitch_mulmods(1 << 15, 21, 3, 7) / 2e9)
