"""Grouped-digit hybrid key switching written out from its definition
(``reference/ckks_ref.py``'s module doc), for the tests: digit keys made
from the gadget's CRT formula, a Python-integer oracle of
``CkksRef.keyswitch``, and decryption of a key switch's output.

Run as a script, it times the reference on the host at the shape the
next CKKS deployment takes (N = 2^15, 21 data primes, dnum = 3, 7 special
primes), on random rows:

    python3 bench/tests/grouped.py
"""
from __future__ import annotations

import math
import os
import sys
import time

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from reference.ckks_ref import CkksRef  # noqa: E402


def col(primes) -> np.ndarray:
    return np.array(primes, dtype=np.uint64).reshape(-1, 1)


def centred(t: np.ndarray, m: int) -> np.ndarray:
    """Object array of residues in [0, m) -> their centred integers."""
    return np.where(t > m // 2, t - m, t)


def groups(ref: CkksRef, dnum: int, basis) -> list[list[int]]:
    """The primes of each digit at ``basis``: the top-level primes in
    consecutive groups of ceil((L+1)/dnum), cut to the basis."""
    g = -(-len(ref.qs) // dnum)
    cut = ([q for q in ref.qs[j:j + g] if q in basis]
           for j in range(0, len(ref.qs), g))
    return [grp for grp in cut if grp]


def gadgets(ref: CkksRef, dnum: int) -> list[int]:
    """T_j = (Q/Q_j) * [(Q/Q_j)^-1]_{Q_j} over the top-level Q."""
    Q = math.prod(ref.qs)
    out = []
    for grp in groups(ref, dnum, ref.qs):
        rest = Q // math.prod(grp)
        out.append(rest * pow(rest, -1, math.prod(grp)))
    return out


def secret(rng, n: int) -> np.ndarray:
    return rng.integers(-1, 2, n).astype(np.int64)


def eval_rows(ref: CkksRef, coeffs: np.ndarray, primes) -> np.ndarray:
    """Integer coefficients -> evaluation rows mod each prime."""
    c = np.asarray(coeffs, dtype=np.int64)
    return ref.ntt(np.stack([(c % p).astype(np.uint64) for p in primes]),
                   primes)


def s_from(ref: CkksRef, s: np.ndarray, primes, g: int | None = None):
    """Evaluation rows of s' = s^2 (``g`` None) or sigma_g(s)."""
    s_ev = eval_rows(ref, s, primes)
    return (s_ev * s_ev % col(primes) if g is None
            else s_ev[:, ref.galois_perm(g)])


def make_key(ref: CkksRef, s: np.ndarray, s_to: np.ndarray, rng, *,
             gadget: list[int], P: int | None = None, e_bound: int = 8):
    """Top-level digit keys (b, a), each (len(gadget), L+1+alpha, n) over
    qs + specials: b_j = e_j - a_j*s + P*T_j*s' with a_j uniform, e_j
    uniform in [-e_bound, e_bound], T_j = gadget[j]; ``s_to`` the
    evaluation rows of s'."""
    full = ref.qs + ref.specials
    q = col(full)
    P = ref.P if P is None else P
    s_ev = eval_rows(ref, s, full)
    bs, as_ = [], []
    for t in gadget:
        a = rng.integers(0, 2**62, (len(full), ref.n),
                         dtype=np.uint64) % q
        e = eval_rows(ref, rng.integers(-e_bound, e_bound + 1, ref.n), full)
        pt = col([P * t % p for p in full])
        bs.append((e + q - a * s_ev % q + pt * s_to % q) % q)
        as_.append(a)
    return np.stack(bs), np.stack(as_)


def at_basis(ref: CkksRef, dnum: int, key, basis):
    """A top-level key cut to ``basis``: its digits there, and the rows of
    the basis and the special primes."""
    rows = [ref.qs.index(q) for q in basis] + list(
        range(len(ref.qs), len(ref.qs) + len(ref.specials)))
    d = len(groups(ref, dnum, basis))
    return tuple(k[:d][:, rows] for k in key)


def oracle_keyswitch(ref: CkksRef, dnum: int, d: np.ndarray, basis,
                     evk_b: np.ndarray, evk_a: np.ndarray):
    """``CkksRef.keyswitch`` in Python integers, rule by rule; only the
    transforms between coefficient and evaluation rows are the
    reference's."""
    basis = tuple(basis)
    full = basis + ref.specials
    k = len(basis)
    dc = ref.intt(d, basis).astype(object)
    row = dict(zip(basis, dc))
    acc0 = np.zeros((len(full), ref.n), dtype=object)
    acc1 = np.zeros((len(full), ref.n), dtype=object)
    for j, grp in enumerate(groups(ref, dnum, basis)):
        Qj = math.prod(grp)
        y = sum(centred(row[q] * pow(Qj // q, -1, q) % q, q) * (Qj // q)
                for q in grp)
        ext = ref.ntt(np.stack([(y % p).astype(np.uint64) for p in full]),
                      full).astype(object)
        acc0 = acc0 + ext * evk_b[j].astype(object)
        acc1 = acc1 + ext * evk_a[j].astype(object)
    P = ref.P
    qs = np.array(full, dtype=object).reshape(-1, 1)
    out = []
    for acc in (acc0 % qs, acc1 % qs):
        low = ref.intt(acc[k:].astype(np.uint64), ref.specials).astype(object)
        z = sum(centred(x * pow(P // p, -1, p) % p, p) * (P // p)
                for x, p in zip(low, ref.specials))
        conv = ref.ntt(np.stack([(z % q).astype(np.uint64) for q in basis]),
                       basis).astype(object)
        pinv = np.array([pow(P, -1, q) for q in basis],
                        dtype=object).reshape(-1, 1)
        qb = qs[:k]
        out.append(((acc[:k] - conv) % qb * pinv % qb).astype(np.uint64))
    return tuple(out)


def decrypt_error(ref: CkksRef, c0, c1, d, s: np.ndarray, s_to, basis):
    """Largest centred coefficient of c0 + c1*s - d*s' mod Q_basis;
    ``s_to``: the evaluation rows of s' over at least the basis."""
    basis = tuple(basis)
    q = col(basis)
    s_ev = eval_rows(ref, s, basis)
    r = (c0 + c1 * s_ev % q + q - d * s_to[:len(basis)] % q) % q
    rc = ref.intt(r, basis).astype(object)
    Q = math.prod(basis)
    x = sum(rc[i] * (Q // p) * pow(Q // p, -1, p) for i, p in enumerate(basis))
    return int(np.max(np.abs(centred(x % Q, Q))))


def error_bound(ref: CkksRef, dnum: int, basis, e_bound: int = 8) -> int:
    """What decrypt_error may read after a key switch with keys of noise
    at most e_bound: the mod-up gives |y_j| <= |group j| * Q_j / 2, so
    the key noise adds sum_j n * |y_j| * e_bound / P; the two mod-downs'
    centred conversions add at most alpha/2 each, the second times s."""
    n, P, alpha = ref.n, ref.P, len(ref.specials)
    key = sum(n * len(g) * math.prod(g) * e_bound
              for g in groups(ref, dnum, basis))
    return -(-key // (2 * P)) + -(-alpha * (n + 1) // 2)


def main() -> int:
    n, levels, dnum, alpha = 1 << 15, 20, 3, 7
    t = time.perf_counter()
    ref = CkksRef(n, levels, "natural", dnum=dnum, special_primes=alpha)
    print(f"CkksRef(n={n}, levels={levels}, dnum={dnum}, "
          f"special_primes={alpha}): {time.perf_counter() - t:.2f} s; "
          f"log2 QP = {math.log2(math.prod(ref.qs) * ref.P):.1f}")
    rng = np.random.default_rng(15)
    basis, full = ref.qs, ref.qs + ref.specials
    s = secret(rng, n)
    t = time.perf_counter()
    key = make_key(ref, s, s_from(ref, s, full), rng,
                   gadget=gadgets(ref, dnum))
    print(f"one grouped relin key {key[0].shape}: "
          f"{time.perf_counter() - t:.2f} s")
    q = col(basis)
    rows = lambda: rng.integers(0, 2**62, (len(basis), n),  # noqa: E731
                                dtype=np.uint64) % q
    a, b = (rows(), rows()), (rows(), rows())
    for name, call in (
            ("multiply", lambda: ref.multiply(a, b, basis, key)),
            ("galois", lambda: ref.galois(a, basis, ref.rotation_element(1),
                                          key)),
            ("key_noise", lambda: ref.key_noise(*key, s, basis))):
        t = time.perf_counter()
        call()
        print(f"{name}: {time.perf_counter() - t:.2f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
