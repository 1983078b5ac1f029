"""Byte counts of the grouped key switch's base conversions and the two
readers of the conversion kernel's ops (``kernel.baseconv_roofline``,
``kernel.baseconv_pct``) on a reduced trace."""
import pytest

from harness import baseconv, spec

N15 = {"n": 32768, "levels": 20, "dnum": 3, "special_primes": 7}
UP = "%base_conv_banks_modup.3 = u32[28,3,32,32768] custom-call(...)"
DOWN = "%base_conv_banks_moddown.{} = u32[21,1,32,32768] custom-call(...)"


def test_conversion_bytes_at_the_configurations_shapes():
    # 3 digits of 7 rows to 28; two mod-downs of 7 rows to 21; n words
    assert baseconv.conversion_bytes(32768, 21, 3, 7) == (
        3 * (7 + 28) * 32768 * 4, 2 * (7 + 21) * 32768 * 4)
    # one-prime digits and one special prime convert nothing
    assert baseconv.conversion_bytes(16384, 8) == (0, 0)
    # an uneven last group of one prime converts nothing either
    assert baseconv.conversion_bytes(1024, 5, 3, 2) == (
        2 * (2 + 5 + 2) * 1024 * 4, 2 * (2 + 5) * 1024 * 4)


def _obs(ops, busy=20.0, ks=10_000):
    return {"window": {"plan": {"key_switches": ks}, "calls": ks // 32},
            "trace": {"ops": ops, "busy_s": busy},
            "peaks": {"hbm_bytes_per_s": 819e9}, "config": N15}


def test_readers_count_only_the_ops_they_see():
    roof = spec.metric_reader("kernel.baseconv_roofline")
    pct = spec.metric_reader("kernel.baseconv_pct")
    up_b, down_b = baseconv.conversion_bytes(32768, 21, 3, 7)
    other = ["%ntt_fwd_banks_pallas.2 = u32[28,12288,256]", 1.5]
    ks = 10_000
    # the mod-up alone among the top ops: its bytes over its time
    obs = _obs([other, [UP, 0.8]])
    assert pct(obs) == pytest.approx(100 * 0.8 / 20.0)
    assert roof(obs) == pytest.approx(100 * ks * up_b / (0.8 * 819e9))
    # one mod-down half shows too: half the mod-down's bytes join
    obs = _obs([other, [UP, 0.8], [DOWN.format(4), 0.3]])
    assert roof(obs) == pytest.approx(
        100 * ks * (up_b + down_b / 2) / (1.1 * 819e9))
    obs = _obs([other, [UP, 0.8], [DOWN.format(4), 0.3], [DOWN.format(5), 0.3]])
    assert pct(obs) == pytest.approx(100 * 1.4 / 20.0)
    assert roof(obs) == pytest.approx(
        100 * ks * (up_b + down_b) / (1.4 * 819e9))


def test_readers_find_nothing_without_the_kernel():
    roof = spec.metric_reader("kernel.baseconv_roofline")
    pct = spec.metric_reader("kernel.baseconv_pct")
    obs = _obs([["%ntt_fwd_banks_pallas.2 = u32[9,32768,128]", 1.7]])
    assert roof(obs) is None and pct(obs) is None
    assert roof(dict(obs, trace=None)) is None
    assert pct(dict(obs, trace=None)) is None
