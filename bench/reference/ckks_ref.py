"""Plain reference of the CKKS ring operations the CKKS cells time.

Straight numpy over uint64 residues, written from the scheme's
definitions and sharing no code with the system under test:

* ring Z_q[X]/(X^n + 1) per prime q, ciphertexts in evaluation form:
  slot j of a row holds the evaluation at psi^(1 + 2*ord(j)), psi the
  primitive 2n-th root g^((q-1)/2n) of the smallest generator g of Z_q*,
  ord(j) = j ("natural" order) or bitrev(j) ("bitrev" order);
* the prime chain: the ``levels + 2`` largest primes q = 1 (mod 2n)
  below 2^30, the largest of them the special prime P;
* hybrid key switching with single-prime digits: digit i of d is the
  centred residue [d]_{q_i}, lifted exactly to every prime of Q*P,
  multiplied into the digit key, summed, then divided by P with rounding
  (the RNS floor of the centred residue mod P);
* Galois automorphism sigma_g as the permutation of evaluation points
  psi^e -> psi^(g*e);
* rescale: the same rounded division by the last prime of the basis;
* the BSGS diagonal matvec of y = x @ W with n1 = ceil(sqrt(d_in))
  baby steps, each diagonal encoded by the canonical embedding.

Every result is an exact integer, so the comparison with the program is
bit for bit.  ``round_mod_down=False`` gives the control: the division by
P floors instead of rounding, which breaks the configuration's stated
"rounded RNS floor" guarantee.
"""
from __future__ import annotations

import math

import numpy as np

_MR_BASES = (2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37)


def is_prime(m: int) -> bool:
    if m < 2:
        return False
    for p in _MR_BASES:
        if m % p == 0:
            return m == p
    d, s = m - 1, 0
    while d % 2 == 0:
        d //= 2
        s += 1
    for a in _MR_BASES:
        x = pow(a, d, m)
        if x in (1, m - 1):
            continue
        for _ in range(s - 1):
            x = x * x % m
            if x == m - 1:
                break
        else:
            return False
    return True


def chain(n: int, levels: int, bits: int = 30) -> tuple[int, tuple[int, ...]]:
    """(P, (q_0..q_L)): the levels+2 largest primes = 1 (mod 2n) below
    2^bits, in descending order; the largest is the special prime."""
    step = 2 * n
    p = ((1 << bits) - 1) // step * step + 1
    out = []
    while len(out) < levels + 2:
        if is_prime(p):
            out.append(p)
        p -= step
    return out[0], tuple(out[1:])


def _generator(q: int) -> int:
    phi, fs, m, d = q - 1, [], q - 1, 2
    while d * d <= m:
        if m % d == 0:
            fs.append(d)
            while m % d == 0:
                m //= d
        d += 1
    if m > 1:
        fs.append(m)
    return next(g for g in range(2, q)
                if all(pow(g, phi // f, q) != 1 for f in fs))


def _bitrev(n: int) -> np.ndarray:
    bits = n.bit_length() - 1
    idx = np.arange(n)
    out = np.zeros(n, dtype=np.int64)
    for b in range(bits):
        out |= ((idx >> b) & 1) << (bits - 1 - b)
    return out


def _powers(base: np.ndarray, q: np.ndarray, n: int) -> np.ndarray:
    """(R, n) table base^i mod q, rows vectorised."""
    t = np.ones((len(q), n), dtype=np.uint64)
    cur = base.astype(np.uint64).reshape(-1, 1)
    qq = q.reshape(-1, 1)
    filled = 1
    while filled < n:
        t[:, filled:2 * filled] = t[:, :filled] * cur % qq
        cur = cur * cur % qq
        filled *= 2
    return t


class CkksRef:
    """Exact CKKS operations over one prime chain (see module doc)."""

    def __init__(self, n: int, levels: int, order: str, *,
                 round_mod_down: bool = True):
        if order not in ("natural", "bitrev"):
            raise ValueError(f"unknown NTT order {order!r}")
        self.n = n
        self.special, self.qs = chain(n, levels)
        self.order = order
        self.round_mod_down = round_mod_down
        self._br = _bitrev(n)
        self._tables: dict = {}

    # ------------------------------------------------------------ NTT

    def _table(self, q: int) -> dict:
        t = self._tables.get(q)
        if t is None:
            n = self.n
            psi = pow(_generator(q), (q - 1) // (2 * n), q)
            qa = np.array([q], dtype=np.uint64)
            ipsi = pow(psi, q - 2, q)
            t = self._tables[q] = {
                "psi": _powers(np.array([psi]), qa, n)[0],
                "ipsi_ninv": _powers(np.array([ipsi]), qa, n)[0]
                * np.uint64(pow(n, q - 2, q)) % np.uint64(q),
                "w": _powers(np.array([psi * psi % q]), qa, n)[0],
                "iw": _powers(np.array([pow(psi * psi % q, q - 2, q)]), qa, n)[0],
            }
        return t

    def _cyclic(self, a: np.ndarray, qcol: np.ndarray, w: np.ndarray):
        """Cyclic DFT per row, natural order in and out."""
        n = self.n
        R = a.shape[0]
        a = a[:, self._br]
        length = 2
        while length <= n:
            half = length // 2
            tw = w[:, (n // length) * np.arange(half)]          # (R, half)
            a = a.reshape(R, n // length, length)
            u = a[..., :half]
            v = a[..., half:] * tw[:, None, :] % qcol[..., None]
            a = np.concatenate([(u + v) % qcol[..., None],
                                (u + qcol[..., None] - v) % qcol[..., None]],
                               axis=2).reshape(R, n)
            length *= 2
        return a

    def ntt(self, a: np.ndarray, primes) -> np.ndarray:
        """Coefficient rows (R, n) -> evaluation rows, row r mod primes[r]."""
        q = np.array(primes, dtype=np.uint64).reshape(-1, 1)
        tabs = [self._table(p) for p in primes]
        psi = np.stack([t["psi"] for t in tabs])
        w = np.stack([t["w"] for t in tabs])
        out = self._cyclic(a.astype(np.uint64) % q * psi % q, q, w)
        return out[:, self._br] if self.order == "bitrev" else out

    def intt(self, a: np.ndarray, primes) -> np.ndarray:
        q = np.array(primes, dtype=np.uint64).reshape(-1, 1)
        tabs = [self._table(p) for p in primes]
        if self.order == "bitrev":
            a = a[:, self._br]
        iw = np.stack([t["iw"] for t in tabs])
        post = np.stack([t["ipsi_ninv"] for t in tabs])
        return self._cyclic(a.astype(np.uint64), q, iw) * post % q

    # ----------------------------------------------------- ring helpers

    @staticmethod
    def _col(primes) -> np.ndarray:
        return np.array(primes, dtype=np.uint64).reshape(-1, 1)

    @staticmethod
    def _lift(row: np.ndarray, src: int, primes, centred: bool = True):
        """Residue row mod src -> rows mod each prime (exact lift of the
        centred, or with ``centred=False`` the plain, representative)."""
        c = row.astype(np.int64)
        if centred:
            c = np.where(c > src // 2, c - src, c)
        return np.stack([(c % p).astype(np.uint64) for p in primes])

    def _divide_last(self, x: np.ndarray, full) -> np.ndarray:
        """Evaluation rows over ``full`` -> division by its last prime,
        rounded (or floored, for the control), over the rest."""
        rest, p_last = tuple(full[:-1]), full[-1]
        last = self.intt(x[-1:], (p_last,))[0]
        ext = self.ntt(self._lift(last, p_last, rest, self.round_mod_down),
                       rest)
        q = self._col(rest)
        inv = np.array([pow(p_last, -1, p) for p in rest],
                       dtype=np.uint64).reshape(-1, 1)
        return (x[:-1] + q - ext) % q * inv % q

    def key_noise(self, evk_b: np.ndarray, evk_a: np.ndarray, s_coeffs,
                  basis, g: int | None = None) -> float:
        """Largest noise of a key-switching key (evk_b, evk_a), each
        (k, k+1, n) evaluation rows over basis + (P,), against the secret
        key with integer coefficients ``s_coeffs``.  Digit i must satisfy
        b_i + a_i*s = e_i + P*T_i*s' with T_i = 1 mod q_i and 0 mod the
        other primes of the basis, and s' = s^2 (``g`` None: the
        relinearisation key) or sigma_g(s).  The noise of a digit is its
        largest centred coefficient where every prime holds the same
        integer; where two primes disagree the integer is at least the
        smallest prime less that, which is what is returned.  A key made
        for another s', g or gadget reads of the order of the primes."""
        full = tuple(basis) + (self.special,)
        k = len(basis)
        q = self._col(full)
        s = np.asarray(s_coeffs, dtype=np.int64)
        s_ev = self.ntt(np.stack([(s % p).astype(np.uint64) for p in full]),
                        full)
        s_from = (s_ev * s_ev % q if g is None
                  else s_ev[:, self.galois_perm(g)])
        worst = 0.0
        for i in range(k):
            b = evk_b[i].astype(np.uint64)
            a = evk_a[i].astype(np.uint64)
            e = (b + a * s_ev % q) % q
            e[i] = (e[i] + q[i] - s_from[i] * np.uint64(self.special % full[i])
                    % q[i]) % q[i]
            ec = self.intt(e, full).astype(np.int64)
            ec = np.where(ec > q.astype(np.int64) // 2,
                          ec - q.astype(np.int64), ec)
            mag = float(np.max(np.abs(ec)))
            if not np.all(ec == ec[0]):
                mag = float(min(full)) - mag
            worst = max(worst, mag)
        return worst

    def keyswitch(self, d: np.ndarray, basis, evk_b: np.ndarray,
                  evk_a: np.ndarray):
        """Switch the key under evaluation rows ``d`` (k, n) over basis;
        evk_b/evk_a: (k, k+1, n) digit keys over basis + (P,)."""
        full = tuple(basis) + (self.special,)
        k = len(basis)
        dc = self.intt(d, basis)
        ext = np.concatenate([self._lift(dc[i], basis[i], full)
                              for i in range(k)])            # (k*(k+1), n)
        ext = self.ntt(ext, full * k).reshape(k, k + 1, self.n)
        q = self._col(full)
        acc0 = np.zeros((k + 1, self.n), dtype=np.uint64)
        acc1 = np.zeros((k + 1, self.n), dtype=np.uint64)
        for i in range(k):
            acc0 = (acc0 + ext[i] * evk_b[i].astype(np.uint64) % q) % q
            acc1 = (acc1 + ext[i] * evk_a[i].astype(np.uint64) % q) % q
        return self._divide_last(acc0, full), self._divide_last(acc1, full)

    # ------------------------------------------------------ scheme ops

    def multiply(self, a, b, basis, relin):
        """a, b: (c0, c1) evaluation rows; relin: (evk_b, evk_a)."""
        q = self._col(basis)
        a0, a1 = (x.astype(np.uint64) for x in a)
        b0, b1 = (x.astype(np.uint64) for x in b)
        d0 = a0 * b0 % q
        d1 = (a0 * b1 % q + a1 * b0 % q) % q
        d2 = a1 * b1 % q
        k0, k1 = self.keyswitch(d2, basis, *relin)
        return (d0 + k0) % q, (d1 + k1) % q

    def rescale(self, c, basis):
        return (self._divide_last(c[0].astype(np.uint64), basis),
                self._divide_last(c[1].astype(np.uint64), basis))

    def galois_perm(self, g: int) -> np.ndarray:
        n = self.n
        j = np.arange(n, dtype=np.int64)
        if self.order == "natural":
            return ((g * (1 + 2 * j)) % (2 * n) - 1) // 2
        e = 1 + 2 * self._br[j]
        return self._br[((g * e) % (2 * n) - 1) // 2]

    def galois(self, c, basis, g: int, key):
        perm = self.galois_perm(g)
        c0 = c[0].astype(np.uint64)[:, perm]
        c1 = c[1].astype(np.uint64)[:, perm]
        k0, k1 = self.keyswitch(c1, basis, *key)
        q = self._col(basis)
        return (c0 + k0) % q, k1

    def rotation_element(self, r: int) -> int:
        return pow(5, r, 2 * self.n)

    # --------------------------------------------------------- matvec

    def encode(self, z: np.ndarray, scale: float, basis) -> np.ndarray:
        """Canonical-embedding encode of up to n/2 complex slots into
        evaluation rows over ``basis``."""
        n = self.n
        ejs = np.array([pow(5, j, 2 * n) for j in range(n // 2)])
        zz = np.zeros(n // 2, dtype=np.complex128)
        zz[:len(z)] = z
        spec = np.zeros(2 * n, dtype=np.complex128)
        spec[ejs] = zz
        spec[2 * n - ejs] = np.conj(zz)
        c = np.fft.fft(spec)[:n].real / n
        c_int = np.rint(c * scale).astype(np.int64)
        rows = np.stack([(c_int % p).astype(np.uint64) for p in basis])
        return self.ntt(rows, basis)

    @staticmethod
    def bsgs_n1(d_in: int) -> int:
        return math.isqrt(d_in - 1) + 1 if d_in > 1 else 1

    def encode_diagonals(self, W: np.ndarray, scale: float, basis) -> dict:
        """(i, j) -> encoded diagonal r = i*n1 + j of W, pre-rotated by
        -i*n1 slots for the giant step."""
        W = np.asarray(W, dtype=np.complex128)
        d_in, d_out = W.shape
        n1 = self.bsgs_n1(d_in)
        m = np.arange(d_out)
        diags = {}
        for r in range(d_in):
            diag = np.zeros(self.n // 2, dtype=np.complex128)
            diag[m] = W[(m + r) % d_in, m]
            if np.any(diag):
                i, j = divmod(r, n1)
                diags[(i, j)] = self.encode(np.roll(diag, i * n1), scale,
                                            basis)
        return diags

    def matvec(self, c, basis, diags: dict, n1: int, keys):
        """BSGS diagonal matvec over ``encode_diagonals`` output;
        ``keys``: rotation amount -> (evk_b, evk_a) Galois key of 5^r."""
        q = self._col(basis)
        babies = {}
        for j in sorted({j for (_, j) in diags}):
            babies[j] = (c if j == 0 else
                         self.galois(c, basis, self.rotation_element(j),
                                     keys[j]))
        inner: dict = {}
        for (i, j), pt in sorted(diags.items()):
            b0, b1 = babies[j]
            t0 = b0.astype(np.uint64) * pt % q
            t1 = b1.astype(np.uint64) * pt % q
            if i in inner:
                inner[i] = ((inner[i][0] + t0) % q, (inner[i][1] + t1) % q)
            else:
                inner[i] = (t0, t1)
        acc0 = np.zeros((len(basis), self.n), dtype=np.uint64)
        acc1 = np.zeros_like(acc0)
        for i, part in sorted(inner.items()):
            if i:
                part = self.galois(part, basis,
                                   self.rotation_element(i * n1),
                                   keys[i * n1])
            acc0 = (acc0 + part[0]) % q
            acc1 = (acc1 + part[1]) % q
        return acc0, acc1
