"""Residue Number System substrate for CKKS (paper §VIII: the full-RNS
variant [35] is what makes a 32-bit datapath sufficient — exactly the
paper's argument for extending NTT-128 to practical FHE).

An ``RnsPoly`` is one device-stacked (k, n) u32 array of residue rows,
one row per prime, in either coefficient or NTT (evaluation) form.  All
ring ops are single vectorized modmath calls over the full stack — the
per-prime moduli ride along as (k, 1) broadcast columns — and the
NTT/iNTT go through the multi-prime "banks" entry points
(``kernels.ops.ntt_banks``), so k residue rows transform in one fused
(prime, batch_tile) dispatch instead of a Python per-row loop.

The host base conversion here lifts a centered residue of ONE 30-bit
prime to another basis, which is exact (the single-prime digits and the
one-prime mod-down of the test oracle ``fhe.keyswitch``); digits of
several primes and several special primes take the device's centred fast
base conversion (``fhe.batched.decompose_banks`` / ``mod_down_banks``).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax.numpy as jnp
import numpy as np

from repro.core.modmath import (addmod, submod, mulmod_barrett, mulmod_shoup,
                                shoup_precompute, barrett_precompute)
from repro.core.params import NTTParams, make_ntt_params, gen_ntt_primes
from repro.kernels import ops


@functools.lru_cache(maxsize=None)
def prime_params(n: int, q: int) -> NTTParams:
    return make_ntt_params(n, q=q)


@functools.lru_cache(maxsize=None)
def basis_pack(primes: tuple[int, ...], n: int) -> dict:
    """Stacked TablePack (see ``fhe.batched``) for a prime basis — the
    twiddle layout the multi-prime bank kernels consume."""
    from repro.fhe.batched import build_table_pack
    return build_table_pack(list(primes), n)


@functools.lru_cache(maxsize=None)
def fourstep_basis_pack(primes: tuple[int, ...], n: int) -> dict:
    """FourStepPack for a prime basis — the factor-table layout of the
    large-N four-step banks pipeline (rings with n >= ops.FOURSTEP_MIN_N
    dispatch through it; see ``RnsPoly.to_ntt``)."""
    from repro.fhe.batched import build_fourstep_pack
    return build_fourstep_pack(list(primes), n)


@functools.lru_cache(maxsize=None)
def _basis_consts(primes: tuple[int, ...]):
    """(k, 1) broadcast columns of q and the Barrett mu per prime."""
    qs = jnp.asarray(np.array(primes, dtype=np.uint32))[:, None]
    mus = np.array([barrett_precompute(q) if (1 << 28) < q < (1 << 30) else 0
                    for q in primes], dtype=np.uint32)
    return qs, jnp.asarray(mus)[:, None]


@dataclasses.dataclass
class RnsPoly:
    """data: (len(primes), n) u32; NTT form iff is_ntt."""
    data: jnp.ndarray
    primes: tuple[int, ...]
    is_ntt: bool

    @property
    def n(self) -> int:
        return self.data.shape[-1]

    @property
    def _q(self) -> jnp.ndarray:
        return _basis_consts(self.primes)[0]

    def _like(self, data, is_ntt: bool | None = None) -> "RnsPoly":
        return RnsPoly(data, self.primes,
                       self.is_ntt if is_ntt is None else is_ntt)

    def add(self, other: "RnsPoly") -> "RnsPoly":
        assert self.primes == other.primes and self.is_ntt == other.is_ntt
        return self._like(addmod(self.data, other.data, self._q))

    def sub(self, other: "RnsPoly") -> "RnsPoly":
        assert self.primes == other.primes and self.is_ntt == other.is_ntt
        return self._like(submod(self.data, other.data, self._q))

    def mul(self, other: "RnsPoly") -> "RnsPoly":
        """Dyadic product — both operands must be in NTT form."""
        assert self.is_ntt and other.is_ntt and self.primes == other.primes
        qs, mus = _basis_consts(self.primes)
        return self._like(mulmod_barrett(self.data, other.data, qs, mus))

    def mul_scalar_per_prime(self, scalars: dict[int, int]) -> "RnsPoly":
        svals = np.array([scalars[q] % q for q in self.primes], dtype=np.uint32)
        sps = np.array([shoup_precompute(int(s), q)
                        for s, q in zip(svals, self.primes)], dtype=np.uint32)
        return self._like(mulmod_shoup(self.data, jnp.asarray(svals)[:, None],
                                       jnp.asarray(sps)[:, None], self._q))

    def neg(self) -> "RnsPoly":
        return self._like(submod(jnp.zeros_like(self.data), self.data, self._q))

    def to_ntt(self) -> "RnsPoly":
        """Negacyclic NTT of every residue row in one banks dispatch.

        Large-N dispatch rule: rings with n >= ``ops.FOURSTEP_MIN_N``
        (2^13, past the single-kernel tile budget) go through the §IX
        four-step banks pipeline and hold *natural-order* NTT rows;
        smaller rings use the single fused kernel (bitrev order).  The
        order is an internal convention per ring size — to_coeff, the
        dyadic ops and key switching all stay inside one convention, so
        the two never mix."""
        assert not self.is_ntt
        if self.n >= ops.FOURSTEP_MIN_N:
            fp = fourstep_basis_pack(self.primes, self.n)
            return self._like(
                ops.ntt_fourstep_banks(self.data, fp, negacyclic=True), True)
        t = basis_pack(self.primes, self.n)
        return self._like(ops.ntt_banks(self.data, t, negacyclic=True), True)

    def to_coeff(self) -> "RnsPoly":
        assert self.is_ntt
        if self.n >= ops.FOURSTEP_MIN_N:
            fp = fourstep_basis_pack(self.primes, self.n)
            return self._like(
                ops.intt_fourstep_banks(self.data, fp, negacyclic=True), False)
        t = basis_pack(self.primes, self.n)
        return self._like(ops.intt_banks(self.data, t, negacyclic=True), False)

    def drop_last(self) -> "RnsPoly":
        return RnsPoly(self.data[:-1], self.primes[:-1], self.is_ntt)

    def automorphism(self, idx) -> "RnsPoly":
        """NTT-domain Galois automorphism: one gather over the stack
        (``ops.galois_banks``); idx from ``core.params.galois_eval_perm``
        for this ring's frequency-order convention."""
        assert self.is_ntt
        return self._like(ops.galois_banks(self.data, idx))


# ------------------------------------------------------- constructions

def from_int_coeffs(coeffs, primes: tuple[int, ...], n: int) -> RnsPoly:
    """coeffs: numpy object/int array of (possibly negative) integers."""
    coeffs = np.asarray(coeffs, dtype=object)
    rows = np.stack([(coeffs % q).astype(np.uint64).astype(np.uint32)
                     for q in primes])
    return RnsPoly(jnp.asarray(rows), tuple(primes), False)


def uniform_ntt(rng: np.random.Generator, primes, n: int) -> RnsPoly:
    """Uniform ring element, sampled directly in NTT form (CRT + NTT are
    bijections, so independent uniform residue rows are exactly uniform)."""
    rows = np.stack([rng.integers(0, q, size=n, dtype=np.uint32)
                     for q in primes])
    return RnsPoly(jnp.asarray(rows), tuple(primes), True)


def gaussian_coeffs(rng: np.random.Generator, n: int, sigma: float = 3.2) -> np.ndarray:
    return np.rint(rng.normal(0.0, sigma, size=n)).astype(np.int64)


def ternary_coeffs(rng: np.random.Generator, n: int) -> np.ndarray:
    return rng.integers(-1, 2, size=n).astype(np.int64)


# ---------------------------------------------------- base conversions

def center_row(row: np.ndarray, q: int) -> np.ndarray:
    """u32 residues -> centered int64 in [-q/2, q/2)."""
    r = row.astype(np.int64)
    return np.where(r > q // 2, r - q, r)


def extend_single(row, src_q: int, dst_primes: tuple[int, ...]):
    """EXACT base conversion of a centered single-prime residue row to
    dst_primes (the alpha=1 'mod-up' of the paper's Fig 22)."""
    c = center_row(np.asarray(row), src_q)
    rows = np.stack([(((c % q) + q) % q).astype(np.uint32)
                     for q in dst_primes])
    return RnsPoly(jnp.asarray(rows), tuple(dst_primes), False)


def crt_reconstruct_centered(poly: RnsPoly) -> np.ndarray:
    """(k, n) residues -> centered big-int numpy object array (host CRT;
    the paper's 'CMOS coprocessor decode' role)."""
    assert not poly.is_ntt
    primes = poly.primes
    Q = 1
    for q in primes:
        Q *= q
    acc = np.zeros(poly.n, dtype=object)
    for row, q in zip(np.asarray(poly.data), primes):
        Qi = Q // q
        t = pow(Qi % q, -1, q)
        acc += row.astype(object) * (Qi * t)
    acc %= Q
    return np.where(acc > Q // 2, acc - Q, acc)


def centered_to_float(big: np.ndarray, scale: float = 1.0) -> np.ndarray:
    """Centered big-int object array -> float64, divided by ``scale``.

    The common case is one vectorized C-level cast (replacing the old
    per-coefficient ``float(x) for x in big`` Python loop in decode);
    the exact object-int path survives only for magnitudes past float64
    range (modulus products beyond ~2^1024).  There each coefficient is
    shifted down to a 64-bit mantissa, divided by the (possibly
    non-integral) scale in float, and rescaled with ``ldexp`` — so the
    division is exact to float64 precision whenever x/scale itself is
    representable, for any basis depth."""
    try:
        return big.astype(np.float64) / scale
    except OverflowError:
        def lift(x):
            a = -x if x < 0 else x
            sh = max(0, a.bit_length() - 64)
            try:
                v = math.ldexp(float(a >> sh) / scale, sh)
            except OverflowError:         # x/scale itself beyond float64:
                v = math.inf              # saturate rather than crash decode
            return -v if x < 0 else v
        return np.array([lift(int(x)) for x in big])


def make_primes(n: int, count: int, bits: int = 30) -> list[int]:
    return gen_ntt_primes(count, n, bits=bits)
