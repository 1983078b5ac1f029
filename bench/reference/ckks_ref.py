"""Plain reference of the CKKS ring operations the CKKS cells time.

Straight numpy over uint64 residues, written from the scheme's
definitions and sharing no code with the system under test; Python
integers appear only in per-prime constants.

* ring Z_q[X]/(X^n + 1) per prime q, ciphertexts in evaluation form:
  slot j of a row holds the evaluation at psi^(1 + 2*ord(j)), psi the
  primitive 2n-th root g^((q-1)/2n) of the smallest generator g of Z_q*,
  ord(j) = j ("natural" order) or bitrev(j) ("bitrev" order);
* the prime chain: the ``levels + 1 + special_primes`` largest primes
  q = 1 (mod 2n) below 2^30, in descending order; the ``special_primes``
  (alpha) largest are the special primes p_1..p_alpha, P their product,
  the rest q_0..q_L.  Key rows are the basis rows, then the special rows
  in chain order;
* hybrid key switching over ``dnum`` digits: the top-level primes
  q_0..q_L fall into consecutive groups of g = ceil((L+1)/dnum), group j
  being q_{jg}..q_{jg+g-1} (the top level so holds ceil((L+1)/g) digits,
  which is dnum where g divides L+1 evenly).  At a lower basis a group
  keeps the primes the basis still holds; empty groups drop out;
* mod-up of digit j, Q_j the product of its primes: the centred fast base
  conversion y = sum_{i in j} [x_i * (Q_j/q_i)^-1]_{q_i, centred} *
  (Q_j/q_i), reduced mod every prime of basis + specials;
* digit key j: b_j + a_j*s = e_j + P*T_j*s' with T_j = (Q/Q_j) *
  [(Q/Q_j)^-1]_{Q_j} over the top-level Q, so T_j = 1 mod the primes of
  group j and 0 mod the other q's; a key at a lower basis is the
  top-level key's rows at that basis and P;
* mod-down: (x - Conv_{P->basis}([x]_P)) * P^-1 per q_i, the conversion
  with centred terms, which rounds the division by P;
* Galois automorphism sigma_g as the permutation of evaluation points
  psi^e -> psi^(g*e);
* rescale: the same rounded division, by the last prime of the basis;
* the BSGS diagonal matvec of y = x @ W with n1 = ceil(sqrt(d_in))
  baby steps, each diagonal encoded by the canonical embedding.

``dnum`` None and ``special_primes`` 1 (the defaults) give one
single-prime digit per limb and one special prime; every rule above then
reads as the plain centred lift of a residue and the rounded division by
one prime.

Every result is an exact integer, so the comparison with the program is
bit for bit.  ``round_mod_down=False`` gives the control: the
conversions of the divisions (mod-down and rescale) keep non-centred
terms, which floors, and breaks the configuration's stated "rounded RNS
floor" guarantee.
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


def chain(n: int, levels: int, special: int = 1,
          bits: int = 30) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """((p_1..p_alpha), (q_0..q_L)): the levels+1+special largest primes
    = 1 (mod 2n) below 2^bits, in descending order; the ``special``
    largest are the special primes.  ValueError where there are fewer."""
    step = 2 * n
    want = levels + 1 + special
    p = ((1 << bits) - 1) // step * step + 1
    out = []
    while len(out) < want and p > step:
        if is_prime(p):
            out.append(p)
        p -= step
    if len(out) < want:
        raise ValueError(f"{len(out)} primes = 1 (mod {step}) below "
                         f"2^{bits}, where {want} are needed")
    return tuple(out[:special]), tuple(out[special:])


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
                 dnum: int | None = None, special_primes: int = 1,
                 round_mod_down: bool = True):
        if order not in ("natural", "bitrev"):
            raise ValueError(f"unknown NTT order {order!r}")
        limbs = levels + 1
        dnum = limbs if dnum is None else dnum
        if not 1 <= dnum <= limbs:
            raise ValueError(f"dnum {dnum} outside 1..levels+1 = 1..{limbs}")
        # primes per digit; P must be at least a digit's modulus
        self.group = -(-limbs // dnum)
        if special_primes < max(1, self.group):
            raise ValueError(
                f"special_primes {special_primes} under max(1, g) = "
                f"{max(1, self.group)}: P below a digit's modulus")
        self.n = n
        self.specials, self.qs = chain(n, levels, special_primes)
        self.P = math.prod(self.specials)
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

    def _convert(self, rows: np.ndarray, src, dst, centred: bool = True):
        """Fast base conversion of coefficient rows (len(src), n), row i
        mod src[i], to rows mod each prime of ``dst``: the integer
        sum_i [x_i * (M/m_i)^-1]_{m_i} * (M/m_i), M the product of src,
        with each bracket's representative centred or, with
        ``centred=False``, in [0, m_i).  From one prime it is the exact
        lift of the centred (or plain) residue."""
        M = math.prod(src)
        d = self._col(dst)
        acc = np.zeros((len(dst), rows.shape[1]), dtype=np.uint64)
        for x, m in zip(rows, src):
            t = x.astype(np.uint64) * np.uint64(pow(M // m % m, -1, m)) \
                % np.uint64(m)
            tt = t[None, :] % d
            if centred:
                tt = np.where(t > m // 2, (tt + d - np.uint64(m) % d) % d, tt)
            f = np.array([M // m % p for p in dst],
                         dtype=np.uint64).reshape(-1, 1)
            acc = (acc + tt * f % d) % d
        return acc

    def _mod_down(self, x: np.ndarray, keep, drop) -> np.ndarray:
        """Evaluation rows over keep + drop -> (x - Conv_{drop->keep}(
        [x]_drop)) * D^-1 over keep, D the product of drop: the division
        by D, rounded (centred terms) or, for the control, floored."""
        keep, drop = tuple(keep), tuple(drop)
        low = self.intt(x[len(keep):], drop)
        ext = self.ntt(self._convert(low, drop, keep, self.round_mod_down),
                       keep)
        q = self._col(keep)
        D = math.prod(drop)
        inv = np.array([pow(D, -1, p) for p in keep],
                       dtype=np.uint64).reshape(-1, 1)
        return (x[:len(keep)] + q - ext) % q * inv % q

    def digits(self, basis) -> list[tuple[int, ...]]:
        """Row positions in ``basis`` of each digit's primes: the
        top-level primes in consecutive groups of ``self.group``, each
        cut to the primes the basis holds, empty groups left out."""
        pos = {q: i for i, q in enumerate(basis)}
        groups = (self.qs[j:j + self.group]
                  for j in range(0, len(self.qs), self.group))
        return [g for g in (tuple(pos[q] for q in grp if q in pos)
                            for grp in groups) if g]

    def key_noise(self, evk_b: np.ndarray, evk_a: np.ndarray, s_coeffs,
                  basis, g: int | None = None) -> float:
        """Largest noise of a key-switching key (evk_b, evk_a), each
        (digits, len(basis) + alpha, n) evaluation rows over basis +
        specials, against the secret key with integer coefficients
        ``s_coeffs``.  Digit j must satisfy b_j + a_j*s = e_j + P*T_j*s'
        with T_j = 1 mod the primes of group j and 0 mod the other primes
        of the basis, and s' = s^2 (``g`` None: the relinearisation key)
        or sigma_g(s).  The noise of a digit is its largest centred
        coefficient where every prime holds the same integer; where two
        primes disagree the integer is at least the smallest prime less
        that, which is what is returned; a key of another shape reads the
        smallest prime.  A key made for another s', g, gadget or P reads
        of the order of the primes."""
        full = tuple(basis) + self.specials
        groups = self.digits(basis)
        shape = (len(groups), len(full), self.n)
        if evk_b.shape != shape or evk_a.shape != shape:
            return float(min(full))
        q = self._col(full)
        s = np.asarray(s_coeffs, dtype=np.int64)
        s_ev = self.ntt(np.stack([(s % p).astype(np.uint64) for p in full]),
                        full)
        s_from = (s_ev * s_ev % q if g is None
                  else s_ev[:, self.galois_perm(g)])
        worst = 0.0
        for j, grp in enumerate(groups):
            b = evk_b[j].astype(np.uint64)
            a = evk_a[j].astype(np.uint64)
            e = (b + a * s_ev % q) % q
            for i in grp:
                e[i] = (e[i] + q[i] - s_from[i] * np.uint64(self.P % full[i])
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
        """Switch the key under evaluation rows ``d`` (len(basis), n) over
        basis; evk_b/evk_a: (digits, len(basis) + alpha, n) digit keys
        over basis + specials (``digits(basis)`` gives the digits)."""
        full = tuple(basis) + self.specials
        groups = self.digits(basis)
        shape = (len(groups), len(full), self.n)
        if evk_b.shape != shape or evk_a.shape != shape:
            raise ValueError(f"keys of shape {evk_b.shape}/{evk_a.shape}, "
                             f"where the basis takes {shape}")
        dc = self.intt(d, basis)
        ext = np.concatenate([self._convert(dc[list(g)],
                                            [basis[i] for i in g], full)
                              for g in groups])
        ext = self.ntt(ext, full * len(groups)).reshape(shape)
        q = self._col(full)
        acc0 = np.zeros((len(full), self.n), dtype=np.uint64)
        acc1 = np.zeros((len(full), self.n), dtype=np.uint64)
        for j in range(len(groups)):
            acc0 = (acc0 + ext[j] * evk_b[j].astype(np.uint64) % q) % q
            acc1 = (acc1 + ext[j] * evk_a[j].astype(np.uint64) % q) % q
        return (self._mod_down(acc0, basis, self.specials),
                self._mod_down(acc1, basis, self.specials))

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
        return (self._mod_down(c[0].astype(np.uint64), basis[:-1],
                               basis[-1:]),
                self._mod_down(c[1].astype(np.uint64), basis[:-1],
                               basis[-1:]))

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
