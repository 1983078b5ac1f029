"""CKKS-RNS scheme built on the SCE-NTT core (paper §II, §VIII).

Host/device split mirrors the paper's Fig 1: key generation, encoding
(canonical embedding) and CRT decode run on the host ("CMOS-FHE
coprocessor"); every ciphertext ring op — NTT, iNTT, dyadic
multiply/add, Galois automorphism, key switch, RNS floor — runs on the
device through a jitted ``fhe.evalplan.EvalPlan`` program over the
banks kernels.  ``multiply``/``rescale``/``rotate``/``conjugate`` each
lower to a single device dispatch; the host-orchestrated digit loop of
``fhe.keyswitch`` survives only as the bit-exact test oracle.

Supported: encode/decode (complex slots), sk/pk encryption, add/sub,
multiply + relinearization (hybrid key switching: ``dnum`` digits of one
or more primes, ``special_primes`` special primes), rescale, slot
rotation and conjugation via Galois automorphisms.  Scale is tracked exactly per
ciphertext, so prime-vs-scale drift cancels in decode.
"""
from __future__ import annotations

import math

import numpy as np
import jax.numpy as jnp

from repro.core.modmath import submod
from repro.core.params import galois_coeff_tables
from repro.fhe import rns
from repro.fhe.evalplan import Ciphertext, EvalPlan, check_same_basis
from repro.fhe.rns import RnsPoly

__all__ = ["Ciphertext", "CkksContext", "galois_int_coeffs", "galois_poly"]


class CkksContext:
    """One CKKS key set over one prime chain.

    The chain is the ``levels + 1 + special_primes`` largest primes
    = 1 (mod 2n) below 2^30, descending: the ``special_primes`` (alpha)
    largest are the special primes p_1..p_alpha (``specials``, P their
    product), the rest q_0..q_L (``qs``).  Hybrid key switching cuts a
    basis into digits: the top-level primes in consecutive groups of
    ``group`` = ceil((L+1)/dnum), each cut to the primes the basis still
    holds (``digit_groups``).  ``dnum=None`` (L+1 digits) and one special
    prime give one single-prime digit per limb."""

    def __init__(self, n: int = 1024, levels: int = 3, scale_bits: int = 28,
                 sigma: float = 3.2, seed: int = 0, dnum: int | None = None,
                 special_primes: int = 1):
        limbs = levels + 1
        self.dnum = limbs if dnum is None else dnum
        if not 1 <= self.dnum <= limbs:
            raise ValueError(f"dnum {self.dnum} outside 1..levels+1 = "
                             f"1..{limbs}")
        self.group = -(-limbs // self.dnum)               # primes per digit
        if special_primes < self.group:
            raise ValueError(
                f"special_primes {special_primes} under the {self.group} "
                "primes of a digit: P would lie below a digit's modulus")
        self.n = n
        self.slots = n // 2
        self.scale = float(1 << scale_bits)
        self.sigma = sigma
        primes = rns.make_primes(n, limbs + special_primes)
        self.specials = tuple(primes[:special_primes])    # largest -> P
        self.qs = tuple(primes[special_primes:])          # q_0 .. q_L
        self.rng = np.random.default_rng(seed)
        # canonical embedding index table: e_j = 5^j mod 2n
        self._ejs = np.array([pow(5, j, 2 * n) for j in range(n // 2)])
        # secret key (ternary), kept host-side; device copies per basis
        self._s_coeffs = rns.ternary_coeffs(self.rng, n)
        # public key at full level
        full = self.qs
        a = rns.uniform_ntt(self.rng, full, n)
        e = self._noise_poly(full)
        s = self._secret_poly(full)
        self.pk = (e.sub(a.mul(s)), a)                    # (b, a) = (-as + e, a)
        self._ksk: dict = {}     # None (relin) | g -> top-level digit keys
        self._plan: EvalPlan | None = None

    @property
    def special(self) -> int:
        """The special prime P of a one-prime gadget."""
        if len(self.specials) != 1:
            raise ValueError(f"{len(self.specials)} special primes: use "
                             "specials")
        return self.specials[0]

    def digit_groups(self, basis) -> list[tuple[int, ...]]:
        """The primes of each key-switch digit at ``basis``: the top-level
        primes in consecutive groups of ``group``, each cut to the primes
        ``basis`` holds, empty groups left out."""
        held = set(basis)
        cut = (tuple(q for q in self.qs[j:j + self.group] if q in held)
               for j in range(0, len(self.qs), self.group))
        return [g for g in cut if g]

    def plan(self) -> EvalPlan:
        """The device-resident evaluation plan for this context (built
        lazily, cached; see ``EvalPlan.prepare`` for eager warm-up)."""
        if self._plan is None:
            self._plan = EvalPlan(self)
        return self._plan

    # ------------------------------------------------------------ keys

    def _secret_poly(self, primes, coeffs=None) -> RnsPoly:
        c = self._s_coeffs if coeffs is None else coeffs
        return rns.from_int_coeffs(c, tuple(primes), self.n).to_ntt()

    def _noise_poly(self, primes) -> RnsPoly:
        return rns.from_int_coeffs(rns.gaussian_coeffs(self.rng, self.n, self.sigma),
                                   tuple(primes), self.n).to_ntt()

    def _make_ksk(self, from_key_coeffs_ntt: RnsPoly, primes: tuple[int, ...]):
        """Digit keys over (primes..., specials), one per digit of
        ``digit_groups(primes)``: evk_j = (-a_j s + e_j + P*T_j*from_key,
        a_j), P the product of the special primes and T_j the CRT gadget
        over Q = prod(primes): 1 mod the primes of digit j, 0 mod the
        others."""
        full = primes + self.specials
        s_full = self._secret_poly(full)
        Q, P = math.prod(primes), math.prod(self.specials)
        evk = []
        # from_key over full basis
        fk = from_key_coeffs_ntt
        for grp in self.digit_groups(primes):
            Qj = math.prod(grp)
            rest = Q // Qj
            PTj = P * (rest * pow(rest % Qj, -1, Qj) % Q)
            a = rns.uniform_ntt(self.rng, full, self.n)
            e = self._noise_poly(full)
            b = e.sub(a.mul(s_full))
            gadget = fk.mul_scalar_per_prime({q: PTj % q for q in full})
            evk.append((b.add(gadget), a))
        return evk

    def _keys_at(self, g: int | None, basis) -> list:
        """Digit keys of s^2 (``g`` None) or sigma_g(s) at ``basis``: made
        once over the top-level basis; a lower basis takes the top-level
        keys of its digits, cut to its own rows and the special rows."""
        if g not in self._ksk:
            full = self.qs + self.specials
            s = self._secret_poly(full)
            fk = (s.mul(s) if g is None else self._secret_poly(
                full, coeffs=galois_int_coeffs(self._s_coeffs, g, self.n)))
            self._ksk[g] = self._make_ksk(fk, self.qs)
        top = self._ksk[g]
        basis = tuple(basis)
        if basis == self.qs:
            return top
        if not set(basis) <= set(self.qs):
            raise ValueError(f"basis {basis} is not within the chain {self.qs}")
        rows = jnp.asarray([self.qs.index(q) for q in basis]
                           + list(range(len(self.qs), len(self.qs)
                                        + len(self.specials))))
        full = basis + self.specials
        return [(RnsPoly(b.data[rows], full, True),
                 RnsPoly(a.data[rows], full, True))
                for (b, a), grp in zip(top, self.digit_groups(self.qs))
                if set(grp) & set(basis)]

    def relin_keys(self, primes: tuple[int, ...]):
        return self._keys_at(None, primes)

    def galois_keys(self, g: int, primes: tuple[int, ...]):
        return self._keys_at(g, primes)

    # -------------------------------------------------- encode / decode

    def encode(self, z, scale: float | None = None,
               basis: tuple[int, ...] | None = None) -> RnsPoly:
        """z: complex array of up to n/2 slots -> plaintext RnsPoly (NTT).

        ``basis`` selects the prime chain of the output (default: the
        full chain) — plaintexts that will meet level-dropped
        ciphertexts (``mul_plain`` operands, ``fhe.linalg`` diagonal
        packs) must be encoded at the ciphertext's basis."""
        scale = scale or self.scale
        basis = tuple(basis if basis is not None else self.qs)
        z = np.asarray(z, dtype=np.complex128)
        zz = np.zeros(self.slots, dtype=np.complex128)
        zz[: len(z)] = z
        n2 = 2 * self.n
        spec = np.zeros(n2, dtype=np.complex128)
        spec[self._ejs] = zz
        spec[n2 - self._ejs] = np.conj(zz)
        c = np.fft.fft(spec)[: self.n].real / self.n
        c_int = np.rint(c * scale).astype(np.int64).astype(object)
        return rns.from_int_coeffs(c_int, basis, self.n).to_ntt()

    def _decode_coeffs(self, coeffs_float: np.ndarray) -> np.ndarray:
        n2 = 2 * self.n
        padded = np.zeros(n2, dtype=np.complex128)
        padded[: self.n] = coeffs_float
        F = np.fft.ifft(padded) * n2
        return F[self._ejs]

    def decode(self, pt: RnsPoly, scale: float) -> np.ndarray:
        big = rns.crt_reconstruct_centered(pt if not pt.is_ntt else pt.to_coeff())
        return self._decode_coeffs(rns.centered_to_float(big, scale))

    # ------------------------------------------------ encrypt / decrypt

    def encrypt(self, pt: RnsPoly, scale: float | None = None) -> Ciphertext:
        scale = scale or self.scale
        primes = pt.primes
        v = rns.from_int_coeffs(rns.ternary_coeffs(self.rng, self.n), primes, self.n).to_ntt()
        e0 = self._noise_poly(primes)
        e1 = self._noise_poly(primes)
        b, a = self.pk
        c0 = b.mul(v).add(e0).add(pt)
        c1 = a.mul(v).add(e1)
        return Ciphertext(c0, c1, scale)

    def decrypt(self, ct: Ciphertext) -> RnsPoly:
        s = self._secret_poly(ct.primes)
        return ct.c0.add(ct.c1.mul(s))

    def decrypt_decode(self, ct: Ciphertext) -> np.ndarray:
        return self.decode(self.decrypt(ct), ct.scale)

    # --------------------------------------------------------- homomorphic

    def add(self, a: Ciphertext, b: Ciphertext) -> Ciphertext:
        check_same_basis("add", a, b, check_scale=True)
        return Ciphertext(a.c0.add(b.c0), a.c1.add(b.c1), a.scale)

    def sub(self, a: Ciphertext, b: Ciphertext) -> Ciphertext:
        check_same_basis("sub", a, b, check_scale=True)
        return Ciphertext(a.c0.sub(b.c0), a.c1.sub(b.c1), a.scale)

    def add_plain(self, a: Ciphertext, pt: RnsPoly) -> Ciphertext:
        return Ciphertext(a.c0.add(pt), a.c1, a.scale)

    def mul_plain(self, a: Ciphertext, pt: RnsPoly, pt_scale: float | None = None) -> Ciphertext:
        pt_scale = pt_scale or self.scale
        return Ciphertext(a.c0.mul(pt), a.c1.mul(pt), a.scale * pt_scale)

    def multiply(self, a: Ciphertext, b: Ciphertext) -> Ciphertext:
        """Tensor + relinearize (paper Table I 'Homomorphic Mult'), one
        jitted device program: dyadic MM/MA + the fused bank-parallel
        key switch (``evalplan.multiply_banks``)."""
        return self.plan().multiply(a, b)

    def rescale(self, a: Ciphertext) -> Ciphertext:
        """RNS floor by q_l, both halves through one fused
        ``mod_down_banks`` pipeline (``evalplan.rescale_banks``)."""
        return self.plan().rescale(a)

    def rotate(self, a: Ciphertext, r: int) -> Ciphertext:
        """Rotate slots left by r (Galois automorphism X -> X^(5^r)),
        applied as an NTT-domain gather + fused key switch."""
        return self.plan().rotate(a, r)

    def conjugate(self, a: Ciphertext) -> Ciphertext:
        return self.plan().conjugate(a)

    # ------------------------------------------------- batched (B cts, 1 dispatch)

    def multiply_many(self, As, Bs) -> list[Ciphertext]:
        """B independent products at one basis as ONE device dispatch
        (``evalplan.multiply_many_banks``); bit-identical to a Python
        loop of ``multiply``."""
        return self.plan().multiply_many(As, Bs)

    def rescale_many(self, cts) -> list[Ciphertext]:
        return self.plan().rescale_many(cts)

    def rotate_many(self, cts, rs) -> list[Ciphertext]:
        """Rotate B ciphertexts by per-ciphertext amounts in one
        dispatch — the batch may mix rotation amounts (per-ciphertext
        Galois gather rows + key digits)."""
        return self.plan().rotate_many(cts, rs)

    def conjugate_many(self, cts) -> list[Ciphertext]:
        return self.plan().conjugate_many(cts)

    def rotate_hoisted(self, a: Ciphertext, rs) -> list[Ciphertext]:
        """R rotations of ONE ciphertext with the key-switch digit
        decomposition hoisted (paid once) — one device dispatch
        (``evalplan.hoisted_rotations_banks``); bit-identical to
        ``[self.rotate(a, r) for r in rs]``."""
        return self.plan().rotate_hoisted(a, rs)


# ------------------------------------------------- Galois automorphism
#
# Coefficient-domain forms.  The device hot path never runs these — it
# uses the NTT-domain gather (``ops.galois_banks``); they serve keygen
# (galois_int_coeffs on the ternary secret) and as the oracle the
# eval-domain path is pinned against.

def galois_int_coeffs(coeffs: np.ndarray, g: int, n: int) -> np.ndarray:
    """sigma_g on integer coefficient vectors: X^t -> X^(g t mod 2n),
    with X^n = -1 folding — one vectorized gather + sign flip."""
    src, pos = galois_coeff_tables(g, n)
    c = np.asarray(coeffs)
    return np.where(pos, c[src], -c[src])


def galois_poly(p: RnsPoly, g: int) -> RnsPoly:
    """Automorphism applied per residue row in the coefficient domain
    (one gather + modular negate over the whole stack), then back to NTT
    form if the input was in NTT form."""
    was_ntt = p.is_ntt
    if was_ntt:
        p = p.to_coeff()
    src, pos = galois_coeff_tables(g, p.n)
    rows = p.data[:, src]
    neg = submod(jnp.zeros_like(rows), rows, p._q)
    res = RnsPoly(jnp.where(jnp.asarray(pos), rows, neg), p.primes, False)
    return res.to_ntt() if was_ntt else res
