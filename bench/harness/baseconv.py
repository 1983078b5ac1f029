"""Bytes the base conversions of a grouped-digit hybrid key switch must
move, computed from the configuration's shapes, and the device time of
the conversion kernel's ops in a reduced trace."""
from __future__ import annotations

from harness import arith

# The names the conversion kernel's ops carry in a profile: the mod-up of
# the digits (one op a relinearising call, every digit) and the mod-down
# from the special primes (one op for each half of the key switch).
MODUP = "base_conv_banks_modup"
MODDOWN = "base_conv_banks_moddown"


def conversion_bytes(n: int, k: int, dnum: int | None = None,
                     alpha: int = 1, word: int = 4) -> tuple[int, int]:
    """(mod-up, mod-down) least HBM bytes of the base conversions of one
    key switch over k primes with the digits of ``arith.digit_sizes(k,
    dnum)`` and ``alpha`` special primes: each digit of m > 1 primes
    reads its m rows and writes k+alpha rows; each of the two mod-downs
    reads alpha rows and writes k rows where alpha > 1.  A row is n
    words.  One-prime digits and one special prime convert nothing."""
    up = sum(m + k + alpha for m in arith.digit_sizes(k, dnum) if m > 1)
    down = 2 * (alpha + k) if alpha > 1 else 0
    return up * n * word, down * n * word


def visible(trace: dict) -> tuple[float, list[float]]:
    """(seconds of the mod-up ops, [seconds of each mod-down op]) among
    the reduced trace's top ops; an op below the reduction's cut is not
    there."""
    ops = trace.get("ops", [])
    return (sum(t for name, t in ops if MODUP in name),
            [t for name, t in ops if MODDOWN in name])


def visible_bytes(config: dict, key_switches: int, trace: dict) -> int:
    """Least bytes of the conversions whose ops the reduced trace shows:
    the mod-up's where its op is there, and half the mod-down's for each
    mod-down op there (each converts one half)."""
    up_b, down_b = conversion_bytes(config["n"], config["levels"] + 1,
                                    config.get("dnum"),
                                    config.get("special_primes", 1))
    up_s, down = visible(trace)
    return key_switches * ((up_b if up_s else 0) + down_b * len(down) // 2)
