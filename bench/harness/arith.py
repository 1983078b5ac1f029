"""Metric arithmetic: percentiles, rates, spreads, and the operation and
byte counts of a hybrid key switch computed from its shapes."""
from __future__ import annotations

import math
import statistics

import numpy as np


def percentile(values, q: float) -> float:
    """q-th percentile by linear interpolation between order statistics
    (numpy's default); an unanswered request enters as +inf."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    pos = (v.size - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, v.size - 1)
    if math.isinf(v[hi]):
        return math.inf if pos > lo or math.isinf(v[lo]) else float(v[lo])
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def rate(count: int, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("rate over an empty window")
    return count / seconds


def spread(values) -> float:
    """Interquartile distance over the median, with the quartiles of
    ``statistics.quantiles(values, n=4)``."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(q2)


def ntt_butterflies(n: int) -> int:
    """Modular multiplications of one radix-2 transform of length n."""
    return n // 2 * (n.bit_length() - 1)


def digit_sizes(k: int, dnum: int | None = None) -> list[int]:
    """Primes per digit of a basis of k primes cut into consecutive
    groups of g = ceil(k/dnum) (``dnum`` None: k single-prime digits)."""
    g = -(-k // (k if dnum is None else dnum))
    return [min(g, k - i) for i in range(0, k, g)]


def conversion_mulmods(m: int, targets: int) -> int:
    """Modular multiplications per coefficient of a fast base conversion
    from m primes to ``targets`` primes: m by (M/m_i)^-1, then m per
    target by M/m_i.  From one prime both factors are 1: none."""
    return 0 if m == 1 else m * (1 + targets)


def keyswitch_mulmods(n: int, k: int, dnum: int | None = None,
                      alpha: int = 1) -> int:
    """Modular multiplications a hybrid key switch over k primes needs,
    from its shapes: the digits of ``digit_sizes(k, dnum)``, ``alpha``
    special primes.  Butterflies of every residue polynomial transformed,
    plus the pointwise products of n; with d digits:

    * k inverse transforms of the input's rows (its digits);
    * d*(k+alpha) forward transforms of the digits' extensions;
    * the mod-up's base conversions (none for one-prime digits);
    * 2*d*(k+alpha) products with the key pair;
    * two mod-downs, one per half: alpha inverse transforms of the
      special rows, their conversion to the k primes (none for one
      special prime), k forward transforms, k multiplications by P^-1.

    At dnum = k, alpha = 1 (single-prime digits, one special prime):
    k + k*(k+1) + 2 + 2*k transforms and 2*k*(k+1) + 2*k products."""
    sizes = digit_sizes(k, dnum)
    ext = k + alpha
    transforms = k + len(sizes) * ext + 2 * (alpha + k)
    pointwise = (sum(conversion_mulmods(m, ext) for m in sizes)
                 + 2 * len(sizes) * ext
                 + 2 * (conversion_mulmods(alpha, k) + k))
    return transforms * ntt_butterflies(n) + pointwise * n


def relin_multiply_bytes(n: int, k: int, batch: int, word: int = 4,
                         dnum: int | None = None, alpha: int = 1) -> int:
    """Least HBM bytes of ``batch`` relinearising multiplies over k
    primes sharing one relin key: read four input halves, write two
    output halves per multiply, read the key pair once: two
    (d, k+alpha, n) tensors, d the digits of ``digit_sizes(k, dnum)``."""
    halves = 6 * k * n * word * batch
    key = 2 * len(digit_sizes(k, dnum)) * (k + alpha) * n * word
    return halves + key
