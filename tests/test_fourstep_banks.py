"""Conformance + property suite for the large-N four-step banks pipeline
(paper §IX on the PR-1 fused kernels; see ``kernels.ops``).

Oracle chain: the single-kernel CG path is pinned to the O(n^2) golden
model in test_ntt_banks / test_ntt; here the multi-pass reference
(``ref.ntt_fourstep_banks_ref``, the CPU default) is pinned bit-exact to
that cg oracle (natural order) for every prime of a three-prime basis at
N in {2^10, 2^12, 2^14}, the one-kernel Pallas transform (interpret
mode) is pinned to the reference at 2^8 and 2^13-2^15, and negacyclic
polymul closes the loop against the schoolbook convolution.  Property
tests run under hypothesis when installed and the hypcompat
deterministic sweep otherwise.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hypcompat import given, settings, st
from repro.core import fourstep as fs
from repro.core.modmath import mulmod_np
from repro.core.ntt import (ntt_cyclic, ntt_negacyclic,
                            negacyclic_convolve_np)
from repro.core.params import (bitrev_perm, fourstep_split, gen_ntt_primes,
                               make_ntt_params)
from repro.fhe import batched as FB
from repro.fhe import rns
from repro.kernels import ops
from repro.kernels.ntt_kernel import fourstep_block

RNG = np.random.default_rng(1404)
SIZES = [1 << 10, 1 << 12, 1 << 14]
K = 3           # primes per basis ("all configured primes" below)


@functools.lru_cache(maxsize=None)
def _basis(n):
    return tuple(gen_ntt_primes(K, n, bits=30))


@functools.lru_cache(maxsize=None)
def _fp(n):
    return FB.build_fourstep_pack(list(_basis(n)), n)


@functools.lru_cache(maxsize=None)
def _unbrev(n):
    return np.argsort(bitrev_perm(n))


def _stack(n, batch=2):
    return np.stack([RNG.integers(0, q, (batch, n), dtype=np.uint32)
                     for q in _basis(n)])


@functools.lru_cache(maxsize=None)
def _jit_fwd(n):
    fp = _fp(n)
    return jax.jit(lambda x: ops.ntt_fourstep_banks(x, fp))


@functools.lru_cache(maxsize=None)
def _jit_inv(n):
    fp = _fp(n)
    return jax.jit(lambda x: ops.intt_fourstep_banks(x, fp))


@functools.lru_cache(maxsize=None)
def _jit_fwd1(n):
    """Single-prime (row 0) jitted pipeline for the polymul tests."""
    fp = FB.slice_fourstep_pack(_fp(n), slice(0, 1))
    return jax.jit(lambda x: ops.ntt_fourstep_banks(x, fp))


@functools.lru_cache(maxsize=None)
def _jit_inv1(n):
    fp = FB.slice_fourstep_pack(_fp(n), slice(0, 1))
    return jax.jit(lambda x: ops.intt_fourstep_banks(x, fp))


# ------------------------------------------------------ oracle conformance

@pytest.mark.parametrize("n", SIZES)
def test_fourstep_banks_vs_cg_oracle(n):
    """Acceptance pin: the banks four-step == the cg_ntt host oracle
    (natural order), bit for bit, cyclic AND negacyclic, every prime.
    (Roundtrip then follows mathematically; test_prop_roundtrip checks
    it at runtime anyway.)"""
    primes, fp = _basis(n), _fp(n)
    x = _stack(n, batch=1)
    for negacyclic in (False, True):
        got = np.asarray(ops.ntt_fourstep_banks(jnp.asarray(x), fp,
                                                negacyclic=negacyclic))
        for i, q in enumerate(primes):
            p = make_ntt_params(n, q=q)
            ref = (ntt_negacyclic if negacyclic else ntt_cyclic)(
                jnp.asarray(x[i]), p)
            want = np.asarray(ref)[..., _unbrev(n)]
            assert np.array_equal(got[i], want), (n, i, negacyclic)


# Bit identity of the one-kernel transform (interpret mode on the CPU)
# with the multi-pass reference: at the three ring sizes the repo runs
# (2^13 and 2^15 split unevenly, 2^14 = 128 x 128) and one small ring,
# lazy and eager, over a batch that leaves the last block of polynomials
# part-filled (``fourstep_block`` + 1 polynomials).
@pytest.mark.parametrize("lazy", [True, False], ids=["lazy", "eager"])
@pytest.mark.parametrize("n", [1 << 8, 1 << 13, 1 << 14, 1 << 15])
def test_fourstep_kernel_equals_reference(n, lazy):
    fp = FB.slice_fourstep_pack(_fp(n), slice(0, 2))
    b = fourstep_block(1 << 20, n) + 1
    x = jnp.asarray(_stack(n, batch=b)[:2])
    for fn in (ops.ntt_fourstep_banks, ops.intt_fourstep_banks):
        got = np.asarray(fn(x, fp, use_pallas=True, lazy=lazy))
        want = np.asarray(fn(x, fp, use_pallas=False))
        assert np.array_equal(got, want), (fn.__name__, n, lazy)


@pytest.mark.parametrize("layout", ["cyclic", "batch_leading"])
def test_fourstep_kernel_layouts(layout):
    """The cyclic transform and a (b, k, ..., n) ciphertext-batch stack
    through the kernel == the reference, at the 2^13 threshold."""
    n = ops.FOURSTEP_MIN_N
    fp = FB.slice_fourstep_pack(_fp(n), slice(0, 2))
    x = _stack(n, batch=6)[:2].reshape(2, 3, 2, n)
    kw = {"negacyclic": False} if layout == "cyclic" else {}
    if layout == "batch_leading":
        x, kw = np.swapaxes(x, 0, 1), {"batch_leading": True}
    x = jnp.asarray(x)
    for fn in (ops.ntt_fourstep_banks, ops.intt_fourstep_banks):
        got = np.asarray(fn(x, fp, use_pallas=True, **kw))
        assert np.array_equal(got, np.asarray(fn(x, fp, use_pallas=False,
                                                 **kw))), fn.__name__


def test_fourstep_block_spans_cover_keyswitch():
    """Every four-step transform of a batched key switch takes the
    one-kernel path: building its programs records
    ``kernels.fourstep.block`` spans (both directions, with the block
    geometry) and no banks pass (``kernels.ntt_banks.block``)."""
    from repro import obs
    n, B = 1 << 8, 3
    full = tuple(gen_ntt_primes(3, n, bits=30))
    d2, evk_b, evk_a = _random_ks_inputs(full, n, B=B)
    t = FB.build_scalar_pack(list(full))
    fsp = FB.build_fourstep_pack(list(full), n)
    n1, n2 = fourstep_split(n)
    jax.clear_caches()
    obs.enable(); obs.clear()
    try:
        FB.batched_keyswitch(jnp.asarray(d2), jnp.asarray(evk_b),
                             jnp.asarray(evk_a), t, fsp=fsp, use_pallas=True)
    finally:
        obs.disable()
    names = [e["name"] for e in obs.events()]
    blocks = [e["args"] for e in obs.events()
              if e["name"] == "kernels.fourstep.block"]
    assert "kernels.ntt_banks.block" not in names
    assert {b["direction"] for b in blocks} == {"fwd", "inv"}
    for b in blocks:
        assert (b["n1"], b["n2"]) == (n1, n2)
        assert b["polys"] == fourstep_block(b["b"], n)


# ------------------------------------------------------------ properties

@settings(max_examples=6)
@given(seed=st.integers(0, 2**31 - 1), n_idx=st.integers(0, len(SIZES) - 1))
def test_prop_roundtrip(seed, n_idx):
    """Property: intt(ntt(x)) == x for random x, every size and prime."""
    n = SIZES[n_idx]
    rng = np.random.default_rng(seed)
    x = np.stack([rng.integers(0, q, n, dtype=np.uint32) for q in _basis(n)])
    back = np.asarray(_jit_inv(n)(_jit_fwd(n)(jnp.asarray(x))))
    assert np.array_equal(back, x)


@settings(max_examples=6)
@given(seed=st.integers(0, 2**31 - 1), c1=st.integers(1, 2**29),
       c2=st.integers(1, 2**29), n_idx=st.integers(0, len(SIZES) - 1))
def test_prop_linearity(seed, c1, c2, n_idx):
    """Property: NTT(c1*x + c2*y) == c1*NTT(x) + c2*NTT(y) mod q."""
    n = SIZES[n_idx]
    primes = _basis(n)
    rng = np.random.default_rng(seed)
    x = np.stack([rng.integers(0, q, n, dtype=np.uint32) for q in primes])
    y = np.stack([rng.integers(0, q, n, dtype=np.uint32) for q in primes])
    qs = np.array(primes, dtype=np.uint64)[:, None]

    def lin(a, b):
        return (((c1 % qs) * a.astype(np.uint64)
                 + (c2 % qs) * b.astype(np.uint64)) % qs).astype(np.uint32)

    fwd = _jit_fwd(n)
    lhs = np.asarray(fwd(jnp.asarray(lin(x, y))))
    fx = np.asarray(fwd(jnp.asarray(x)))
    fy = np.asarray(fwd(jnp.asarray(y)))
    assert np.array_equal(lhs, lin(fx, fy))


@settings(max_examples=1)
@given(seed=st.integers(0, 2**31 - 1))
def test_prop_polymul_schoolbook(seed):
    """Property: negacyclic polymul through the four-step pipeline ==
    the O(n^2) schoolbook convolution (first prime, N=2^10; larger N are
    covered by the cross-oracle test below — schoolbook there is
    O(minutes) of host Python)."""
    n = 1 << 10
    q = _basis(n)[0]
    rng = np.random.default_rng(seed)
    a = rng.integers(0, q, n, dtype=np.uint32)
    b = rng.integers(0, q, n, dtype=np.uint32)
    A = _jit_fwd1(n)(jnp.asarray(a)[None])
    B = _jit_fwd1(n)(jnp.asarray(b)[None])
    C = mulmod_np(np.asarray(A), np.asarray(B), q)
    got = np.asarray(_jit_inv1(n)(jnp.asarray(C)))[0]
    assert np.array_equal(got, negacyclic_convolve_np(a, b, q))


@pytest.mark.parametrize("n", SIZES[1:])
def test_polymul_cross_oracle_large(n):
    """Negacyclic polymul at 2^12/2^14 == the single-kernel negacyclic
    path (itself schoolbook/golden-model-pinned at small N), compared in
    the order-free coefficient domain."""
    q = _basis(n)[0]
    p = make_ntt_params(n, q=q)
    a = RNG.integers(0, q, n, dtype=np.uint32)
    b = RNG.integers(0, q, n, dtype=np.uint32)
    # four-step route
    A = _jit_fwd1(n)(jnp.asarray(a)[None])
    B = _jit_fwd1(n)(jnp.asarray(b)[None])
    C = mulmod_np(np.asarray(A), np.asarray(B), q)
    got = np.asarray(_jit_inv1(n)(jnp.asarray(C)))[0]
    # single-kernel route (bitrev NTT domain — order cancels in coeffs)
    from repro.core.ntt import intt_negacyclic
    A2 = ntt_negacyclic(jnp.asarray(a), p)
    B2 = ntt_negacyclic(jnp.asarray(b), p)
    C2 = mulmod_np(np.asarray(A2), np.asarray(B2), q)
    want = np.asarray(intt_negacyclic(jnp.asarray(C2), p))
    assert np.array_equal(got, want)


# --------------------------------------------------- FHE-layer dispatch

def test_rnspoly_large_n_dispatch():
    """RnsPoly.to_ntt at n >= FOURSTEP_MIN_N routes through the
    four-step pipeline (natural-order rows) and roundtrips exactly."""
    n = ops.FOURSTEP_MIN_N                     # 2^13: the threshold itself
    primes = tuple(gen_ntt_primes(2, n, bits=30))
    coeffs = RNG.integers(-(1 << 20), 1 << 20, size=n).astype(np.int64)
    poly = rns.from_int_coeffs(coeffs, primes, n)
    pn = poly.to_ntt()
    # natural-order check against the cg oracle for row 0
    p0 = make_ntt_params(n, q=primes[0])
    want = np.asarray(ntt_negacyclic(poly.data[0], p0))[_unbrev(n)]
    assert np.array_equal(np.asarray(pn.data[0]), want)
    back = pn.to_coeff()
    assert np.array_equal(np.asarray(back.data), np.asarray(poly.data))


def _random_ks_inputs(full, n, B=1):
    basis = full[:-1]
    k = len(basis)
    d2 = RNG.integers(0, 2**31, (k, B, n)).astype(np.uint32)
    for i, q in enumerate(basis):
        d2[i] %= q
    evk_b = RNG.integers(0, 2**31, (k, k + 1, n)).astype(np.uint32)
    evk_a = RNG.integers(0, 2**31, (k, k + 1, n)).astype(np.uint32)
    for j, q in enumerate(full):
        evk_b[:, j] %= q
        evk_a[:, j] %= q
    return d2, evk_b, evk_a


def test_batched_keyswitch_fourstep_wiring():
    """``batched_keyswitch(fsp=...)`` == a straightforward per-digit
    four-step oracle (same transform primitives, plain Python wiring):
    pins the digit fold, transposes, inner product and mod-down of the
    large-N path.  Small n keeps it cheap — the fsp path is the same
    code at every size; the 2^13 host-oracle pin runs in the slow suite
    (test_batched_keyswitch_large_n_matches_host_oracle)."""
    from repro.core.modmath import addmod, submod, mulmod_barrett, mulmod_shoup
    from repro.fhe.batched import extend_centered
    n = 512
    full = tuple(gen_ntt_primes(3, n, bits=30))
    k = len(full) - 1
    d2, evk_b, evk_a = _random_ks_inputs(full, n)
    t = FB.build_scalar_pack(list(full))   # fsp path needs no twiddle rows
    fsp = FB.build_fourstep_pack(list(full), n)
    fused = jax.jit(lambda d, eb, ea: FB.batched_keyswitch(d, eb, ea, t, fsp=fsp))
    ks0, ks1 = fused(jnp.asarray(d2), jnp.asarray(evk_b), jnp.asarray(evk_a))

    # per-digit oracle on the same four-step primitives
    fsb = FB.slice_fourstep_pack(fsp, slice(0, k))
    fsl = FB.slice_fourstep_pack(fsp, slice(k, k + 1))

    @jax.jit
    def oracle(d2, evk_b, evk_a):
        mu = t["mu"][:, None]
        qcol = t["qs"][:, None]
        acc0 = acc1 = None
        for i in range(k):
            ci = ops.intt_fourstep_banks(
                d2[i:i + 1, 0], FB.slice_fourstep_pack(fsp, slice(i, i + 1)))
            ext = extend_centered(ci[0], t["qs"][i], t["qs"])   # (k+1, n)
            y = ops.ntt_fourstep_banks(ext, fsp)
            t0 = mulmod_barrett(y, evk_b[i], qcol, mu)
            t1 = mulmod_barrett(y, evk_a[i], qcol, mu)
            acc0 = t0 if acc0 is None else addmod(acc0, t0, qcol)
            acc1 = t1 if acc1 is None else addmod(acc1, t1, qcol)

        def mod_down(acc):
            lastc = ops.intt_fourstep_banks(acc[k:], fsl)
            ext = extend_centered(lastc[0], t["qs"][k], t["qs"][:k])
            extn = ops.ntt_fourstep_banks(ext, fsb)
            d = submod(acc[:k], extn, t["qs"][:k, None])
            return mulmod_shoup(d, t["pinv"][:, None], t["pinv_p"][:, None],
                                t["qs"][:k, None])

        return mod_down(acc0), mod_down(acc1)

    w0, w1 = oracle(jnp.asarray(d2), jnp.asarray(evk_b), jnp.asarray(evk_a))
    assert np.array_equal(np.asarray(ks0)[:, 0], np.asarray(w0))
    assert np.array_equal(np.asarray(ks1)[:, 0], np.asarray(w1))


@pytest.mark.slow  # ~20 s: full host RnsPoly oracle at the 2^13 threshold
def test_batched_keyswitch_large_n_matches_host_oracle():
    """The fused large-N key switch (fsp four-step pack) == the host
    RnsPoly oracle at n = 2^13, bit for bit — the §IX key-switch
    pipeline running end to end on the large-N kernels."""
    from repro.fhe.keyswitch import keyswitch as host_keyswitch
    from repro.fhe.rns import RnsPoly
    n = ops.FOURSTEP_MIN_N
    full = tuple(gen_ntt_primes(3, n, bits=30))  # 2 basis + special
    basis, special = full[:-1], full[-1]
    k = len(basis)
    d2, evk_b, evk_a = _random_ks_inputs(full, n)
    t = FB.build_scalar_pack(list(full))
    fsp = FB.build_fourstep_pack(list(full), n)
    evk_host = [(RnsPoly(jnp.asarray(evk_b[i]), full, True),
                 RnsPoly(jnp.asarray(evk_a[i]), full, True))
                for i in range(k)]
    h0, h1 = host_keyswitch(RnsPoly(jnp.asarray(d2[:, 0]), basis, True),
                            evk_host, special)
    ks0, ks1 = FB.batched_keyswitch(jnp.asarray(d2), jnp.asarray(evk_b),
                                    jnp.asarray(evk_a), t, fsp=fsp)
    assert np.array_equal(np.asarray(ks0)[:, 0], np.asarray(h0.data))
    assert np.array_equal(np.asarray(ks1)[:, 0], np.asarray(h1.data))


def test_fourstep_split_shapes():
    """Factorization invariants incl. the paper's 2^14 = 128 x 128."""
    assert fourstep_split(1 << 14) == (128, 128)
    assert fourstep_split(1 << 13) == (128, 64)
    assert fourstep_split(1 << 10) == (32, 32)
    for s in range(4, 16):
        n1, n2 = fourstep_split(1 << s)
        assert n1 * n2 == 1 << s and n1 >= n2
