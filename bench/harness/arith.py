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


def keyswitch_mulmods(n: int, k: int) -> int:
    """Modular multiplications a hybrid key switch with k single-prime
    digits over k primes plus one special prime needs, from its shapes:
    butterflies of every residue polynomial transformed (k inverse, k*(k+1)
    forward for the digit extension, 2 inverse and 2*k forward for the two
    divisions by P) plus the pointwise products (2*k*(k+1) digit-key
    products, 2*k multiplications by P^-1)."""
    polys = k + k * (k + 1) + 2 + 2 * k
    pointwise = 2 * k * (k + 1) + 2 * k
    return polys * ntt_butterflies(n) + pointwise * n


def relin_multiply_bytes(n: int, k: int, batch: int, word: int = 4) -> int:
    """Least HBM bytes of ``batch`` relinearising multiplies sharing one
    relin key: read four input halves, write two output halves per
    multiply, read the (k, k+1, n) key pair once."""
    halves = 6 * k * n * word * batch
    key = 2 * k * (k + 1) * n * word
    return halves + key
