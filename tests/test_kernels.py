"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (exact equality).

Pallas kernels execute in interpret mode on CPU; the oracle is ref.py,
which is itself validated against numpy/bruteforce in test_ntt.py —
a two-level oracle chain."""
import numpy as np
import jax.numpy as jnp
import pytest

from hypcompat import given, settings, st

from repro.core.params import galois_eval_perm, gen_ntt_primes, make_ntt_params
from repro.kernels import ops, ref

RNG = np.random.default_rng(123)


def _rand(p, batch):
    return RNG.integers(0, p.q, size=(batch, p.n), dtype=np.uint32)


@pytest.mark.parametrize(
    "n", [16, 128, 1024, pytest.param(4096, marks=pytest.mark.slow)])
@pytest.mark.parametrize("batch", [1, 8, 13])
@pytest.mark.parametrize("negacyclic", [False, True])
def test_ntt_fwd_kernel_sweep(n, batch, negacyclic):
    p = make_ntt_params(n)
    x = _rand(p, batch)
    got = np.asarray(ops.ntt(jnp.asarray(x), p, negacyclic=negacyclic, use_pallas=True))
    want = np.asarray(ref.ntt_fwd_ref(x, p, negacyclic))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("n", [16, 128, 1024])
@pytest.mark.parametrize("batch", [1, 8, 13])
@pytest.mark.parametrize("negacyclic", [False, True])
def test_ntt_inv_kernel_sweep(n, batch, negacyclic):
    p = make_ntt_params(n)
    x = _rand(p, batch)
    got = np.asarray(ops.intt(jnp.asarray(x), p, negacyclic=negacyclic, use_pallas=True))
    want = np.asarray(ref.ntt_inv_ref(x, p, negacyclic))
    assert np.array_equal(got, want)


@pytest.mark.parametrize(
    "n", [128, pytest.param(2048, marks=pytest.mark.slow)])
def test_kernel_roundtrip(n):
    p = make_ntt_params(n)
    x = _rand(p, 8)
    y = ops.ntt(jnp.asarray(x), p, negacyclic=True, use_pallas=True)
    back = np.asarray(ops.intt(y, p, negacyclic=True, use_pallas=True))
    assert np.array_equal(back, x)


@pytest.mark.parametrize("n", [128, 1024])
@pytest.mark.parametrize("batch", [1, 8, 9])
def test_dyadic_mul_kernel(n, batch):
    p = make_ntt_params(n)
    a, b = _rand(p, batch), _rand(p, batch)
    got = np.asarray(ops.dyadic_mul(jnp.asarray(a), jnp.asarray(b), p, use_pallas=True))
    want = np.asarray(ref.dyadic_mul_ref(a, b, p.q, p.barrett_mu))
    assert np.array_equal(got, want)
    # and against exact u64 numpy
    assert np.array_equal(got, (a.astype(np.uint64) * b % p.q).astype(np.uint32))


@pytest.mark.parametrize("n", [128])
def test_dyadic_mac_kernel(n):
    p = make_ntt_params(n)
    acc, a, b = _rand(p, 8), _rand(p, 8), _rand(p, 8)
    got = np.asarray(ops.dyadic_mac(jnp.asarray(acc), jnp.asarray(a), jnp.asarray(b), p, use_pallas=True))
    want = (acc.astype(np.uint64) + a.astype(np.uint64) * b % p.q) % p.q
    assert np.array_equal(got, want.astype(np.uint32))


def test_mixed_leading_dims():
    p = make_ntt_params(128)
    x = RNG.integers(0, p.q, size=(3, 5, 128), dtype=np.uint32)
    got = np.asarray(ops.ntt(jnp.asarray(x), p, negacyclic=True, use_pallas=True))
    want = np.asarray(ref.ntt_fwd_ref(x, p, True))
    assert np.array_equal(got, want)


# --------------------------------------- galois_banks shape edge cases
#
# The Galois automorphism is one XLA gather on every backend (Mosaic
# has no lane gather past 128 lanes, so there is no Pallas kernel).
# These sweeps drive batch sizes of every residue mod 8, the single-row
# batch, and >2-D middle dims, pinned against the numpy gather.

_GAL_N, _GAL_K = 128, 2
_GAL_PRIMES = gen_ntt_primes(_GAL_K, _GAL_N, bits=30)
_GAL_GS = [5, 25, 2 * _GAL_N - 1, 9]


def _gal_x(mid):
    return np.stack([RNG.integers(0, q, tuple(mid) + (_GAL_N,), dtype=np.uint32)
                     for q in _GAL_PRIMES])


@settings(max_examples=20)
@given(st.integers(1, 19), st.integers(0, 3))
def test_galois_banks_batch_tile_sweep(batch, g):
    """Shared gather row: any batch size and automorphism == the numpy
    gather exactly."""
    x = _gal_x((batch,))
    idx = galois_eval_perm(_GAL_GS[g], _GAL_N, False)
    got = np.asarray(ops.galois_banks(jnp.asarray(x), idx))
    assert np.array_equal(got, x[..., idx]), (batch, g)


@settings(max_examples=20)
@given(st.integers(1, 19), st.integers(0, 3))
def test_galois_banks_multi_idx_sweep(batch, g0):
    """Per-batch gather rows (mixed automorphisms): row j permutes
    batch row j of every prime row."""
    x = _gal_x((batch,))
    idx = np.stack([galois_eval_perm(_GAL_GS[(g0 + i) % 4], _GAL_N, False)
                    for i in range(batch)]).astype(np.int32)
    got = np.asarray(ops.galois_banks(jnp.asarray(x), jnp.asarray(idx)))
    want = np.stack([x[:, i, idx[i]] for i in range(batch)], axis=1)
    assert np.array_equal(got, want), (batch, g0)


@pytest.mark.parametrize("mid", [(1,), (2, 3), (3, 2, 2), (1, 1)])
def test_galois_banks_highdim_mid(mid):
    """>2-D middle dims keep their shape through the gather."""
    x = _gal_x(mid)
    idx = galois_eval_perm(5, _GAL_N, False)
    got = np.asarray(ops.galois_banks(jnp.asarray(x), idx))
    assert got.shape == x.shape
    assert np.array_equal(got, x[..., idx])


def test_galois_banks_batch_leading_matches_prime_major():
    x = _gal_x((5,))
    idx = galois_eval_perm(25, _GAL_N, False)
    lead = jnp.asarray(np.swapaxes(x, 0, 1))          # (b, k, n)
    got = np.asarray(ops.galois_banks(lead, idx, batch_leading=True))
    want = np.asarray(ops.galois_banks(jnp.asarray(x), idx))
    assert np.array_equal(got, np.swapaxes(want, 0, 1))


def test_banks_batch_leading_matches_prime_major():
    """Every (b, k, n) leading-batch entry point == swapaxes of the
    prime-major call, both dispatch paths (the ciphertext-batch axis
    convention the batched EvalPlan programs ride on)."""
    from repro.fhe import batched as FB
    t = FB.build_table_pack(list(_GAL_PRIMES), _GAL_N)
    x = jnp.asarray(np.swapaxes(_gal_x((5,)), 0, 1))           # (b, k, n)
    fp = FB.build_fourstep_pack(list(_GAL_PRIMES), _GAL_N)
    fns = [lambda v, kw: ops.ntt_banks(v, t, **kw),
           lambda v, kw: ops.intt_banks(v, t, **kw),
           lambda v, kw: ops.ntt_fourstep_banks(v, fp, **kw),
           lambda v, kw: ops.intt_fourstep_banks(v, fp, **kw)]
    for up in (False, True):
        for fn in fns:
            got = np.asarray(fn(x, dict(batch_leading=True, use_pallas=up)))
            want = np.asarray(fn(jnp.swapaxes(x, 0, 1),
                                 dict(use_pallas=up)))
            assert np.array_equal(got, np.swapaxes(want, 0, 1)), (fn, up)


def test_fourstep_banks_batch_leading_matches_prime_major():
    from repro.core.params import gen_ntt_primes as gen
    from repro.fhe import batched as FB
    n = ops.FOURSTEP_MIN_N
    primes = gen(2, n, bits=30)
    fp = FB.build_fourstep_pack(primes, n)
    x = np.stack([RNG.integers(0, q, (3, n), dtype=np.uint32) for q in primes])
    lead = jnp.asarray(np.swapaxes(x, 0, 1))
    got = np.asarray(ops.ntt_fourstep_banks(lead, fp, batch_leading=True))
    want = np.asarray(ops.ntt_fourstep_banks(jnp.asarray(x), fp))
    assert np.array_equal(got, np.swapaxes(want, 0, 1))
    back = np.asarray(ops.intt_fourstep_banks(jnp.asarray(got), fp,
                                              batch_leading=True))
    assert np.array_equal(np.swapaxes(back, 0, 1), x)
