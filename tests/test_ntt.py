import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypcompat import given, settings, st

from repro.core import ntt as N
from repro.core.params import make_ntt_params, gen_ntt_primes, bitrev_perm
from repro.core.modmath import mulmod_np

RNG = np.random.default_rng(7)


def _rand_poly(p, batch=()):
    return RNG.integers(0, p.q, size=batch + (p.n,), dtype=np.uint32)


@pytest.mark.parametrize("n", [4, 16, 128, 256])
def test_cg_ntt_matches_brute_force(n):
    """Paper §VII.C: CG network output == brute-force eq.(1), bit-reversed."""
    p = make_ntt_params(n)
    a = _rand_poly(p)
    got = np.asarray(N.ntt_cyclic(jnp.asarray(a), p))
    ref = N.brute_ntt_bitrev_np(a, p.omega, p.q)
    assert np.array_equal(got, ref)


@pytest.mark.parametrize(
    "n", [128, 1024, pytest.param(8192, marks=pytest.mark.slow)])
def test_roundtrip(n):
    p = make_ntt_params(n)
    a = _rand_poly(p, batch=(4,))
    back = np.asarray(N.intt_cyclic(N.ntt_cyclic(jnp.asarray(a), p), p))
    assert np.array_equal(back, a)


@pytest.mark.parametrize("n", [128, 4096])
def test_negacyclic_roundtrip(n):
    p = make_ntt_params(n)
    a = _rand_poly(p, batch=(3,))
    back = np.asarray(N.intt_negacyclic(N.ntt_negacyclic(jnp.asarray(a), p), p))
    assert np.array_equal(back, a)


def test_convolution_theorem_negacyclic():
    """intt(ntt(a) .* ntt(b)) == negacyclic schoolbook convolution."""
    p = make_ntt_params(128)
    a, b = _rand_poly(p), _rand_poly(p)
    A = N.ntt_negacyclic(jnp.asarray(a), p)
    B = N.ntt_negacyclic(jnp.asarray(b), p)
    C = mulmod_np(np.asarray(A), np.asarray(B), p.q)
    got = np.asarray(N.intt_negacyclic(jnp.asarray(C), p))
    assert np.array_equal(got, N.negacyclic_convolve_np(a, b, p.q))


def test_linearity():
    p = make_ntt_params(256)
    a, b = _rand_poly(p), _rand_poly(p)
    c = int(RNG.integers(1, p.q))
    lhs = N.ntt_cyclic(jnp.asarray((a.astype(np.uint64) * c % p.q).astype(np.uint32)), p)
    rhs = mulmod_np(np.asarray(N.ntt_cyclic(jnp.asarray(a), p)), c, p.q)
    assert np.array_equal(np.asarray(lhs), rhs)
    s = ((a.astype(np.uint64) + b.astype(np.uint64)) % p.q).astype(np.uint32)
    lhs2 = np.asarray(N.ntt_cyclic(jnp.asarray(s), p))
    rhs2 = (np.asarray(N.ntt_cyclic(jnp.asarray(a), p)).astype(np.uint64)
            + np.asarray(N.ntt_cyclic(jnp.asarray(b), p)).astype(np.uint64)) % p.q
    assert np.array_equal(lhs2, rhs2.astype(np.uint32))


def test_batch_10k_random_vs_oracle_ntt128():
    """Scaled-down version of the paper's 1e5 random validation: batch
    CG-NTT-128 against the O(n^2) golden model (exact)."""
    p = make_ntt_params(128)
    a = _rand_poly(p, batch=(128,))
    got = np.asarray(N.ntt_cyclic(jnp.asarray(a), p))
    perm = bitrev_perm(128)
    # vectorized O(n^2) oracle via object matrix once
    ref = N.brute_ntt_np(a, p.omega, p.q)[:, perm]
    assert np.array_equal(got, ref)


@pytest.mark.parametrize("bits", [29, 30])
def test_multiple_primes(bits):
    for q in gen_ntt_primes(2, 128, bits=bits):
        p = make_ntt_params(128, q=q, bits=bits)
        a = _rand_poly(p)
        back = np.asarray(N.intt_cyclic(N.ntt_cyclic(jnp.asarray(a), p), p))
        assert np.array_equal(back, a)


@settings(max_examples=20)
@given(st.integers(0, 2**31))
def test_property_impulse(seed):
    """NTT of a scaled unit impulse at 0 is constant; property holds for
    any amplitude (hypothesis-driven)."""
    p = make_ntt_params(64)
    amp = seed % p.q
    a = np.zeros(64, dtype=np.uint32)
    a[0] = amp
    got = np.asarray(N.ntt_cyclic(jnp.asarray(a), p))
    assert np.all(got == amp)


def test_parseval_like_energy_preservation():
    """n * sum(a_i^2) == sum(A_k * conj... over Z_q: use roundtrip of the
    squared transform instead — intt(ntt(a)^2 pointwise) == a * a cyclic."""
    p = make_ntt_params(64)
    a = _rand_poly(p)
    A = np.asarray(N.ntt_cyclic(jnp.asarray(a), p))
    C = mulmod_np(A, A, p.q)
    got = np.asarray(N.intt_cyclic(jnp.asarray(C), p))
    # cyclic self-convolution oracle
    n = 64
    ref = [0] * n
    for i in range(n):
        for j in range(n):
            ref[(i + j) % n] = (ref[(i + j) % n] + int(a[i]) * int(a[j])) % p.q
    assert np.array_equal(got, np.array(ref, dtype=np.uint32))
