"""EvalPlan conformance: the jitted device-resident scheme programs
(fhe.evalplan) pinned bit-exact against the pre-refactor host
compositions — host-loop ``keyswitch``, ``mod_down_by_last`` and the
coefficient-domain ``galois_poly`` — at the CG (2^10) and four-step
(2^14, slow suite) ring sizes, plus unit pins for the new
``galois_banks`` gather kernel and the vectorized Galois / decode
helpers."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.params import (galois_coeff_tables, galois_eval_perm,
                               gen_ntt_primes)
from repro.fhe import batched as FB
from repro.fhe import rns
from repro.fhe.ckks import CkksContext, Ciphertext, galois_int_coeffs, galois_poly
from repro.fhe.evalplan import EvalPlan
from repro.fhe.keyswitch import keyswitch as host_keyswitch
from repro.fhe.keyswitch import mod_down_by_last
from repro.fhe.rns import RnsPoly
from repro.kernels import ops

RNG = np.random.default_rng(23)


# --------------------------------------------- pre-refactor compositions
#
# The exact op sequences CkksContext.multiply/rescale/rotate ran before
# the EvalPlan refactor, built from the host-oracle modules that remain
# in-tree as test pins.

def old_multiply(ctx, a, b):
    d0 = a.c0.mul(b.c0)
    d1 = a.c0.mul(b.c1).add(a.c1.mul(b.c0))
    d2 = a.c1.mul(b.c1)
    ks0, ks1 = host_keyswitch(d2, ctx.relin_keys(a.primes), ctx.special)
    return Ciphertext(d0.add(ks0), d1.add(ks1), a.scale * b.scale)


def old_rescale(ctx, a):
    return Ciphertext(mod_down_by_last(a.c0), mod_down_by_last(a.c1),
                      a.scale / a.primes[-1])


def old_apply_galois(ctx, a, g):
    c0g = galois_poly(a.c0, g)
    c1g = galois_poly(a.c1, g)
    ks0, ks1 = host_keyswitch(c1g, ctx.galois_keys(g, a.primes), ctx.special)
    return Ciphertext(c0g.add(ks0), ks1, a.scale)


def _ct_equal(a, b):
    return (np.array_equal(np.asarray(a.c0.data), np.asarray(b.c0.data))
            and np.array_equal(np.asarray(a.c1.data), np.asarray(b.c1.data)))


def _pin_scheme_ops(ctx, r, atol=1e-3):
    rng = np.random.default_rng(31)
    z1 = rng.uniform(-1, 1, ctx.slots) + 1j * rng.uniform(-1, 1, ctx.slots)
    z2 = rng.uniform(-1, 1, ctx.slots) + 1j * rng.uniform(-1, 1, ctx.slots)
    ct1 = ctx.encrypt(ctx.encode(z1))
    ct2 = ctx.encrypt(ctx.encode(z2))

    prod = ctx.multiply(ct1, ct2)
    assert _ct_equal(prod, old_multiply(ctx, ct1, ct2))
    rs = ctx.rescale(prod)
    want_rs = old_rescale(ctx, prod)
    assert _ct_equal(rs, want_rs) and rs.scale == want_rs.scale
    rot = ctx.rotate(ct1, r)
    assert _ct_equal(rot, old_apply_galois(ctx, ct1, pow(5, r, 2 * ctx.n)))
    conj = ctx.conjugate(ct1)
    assert _ct_equal(conj, old_apply_galois(ctx, ct1, 2 * ctx.n - 1))
    # the rescaled product still decodes to the slotwise product
    got = ctx.decrypt_decode(rs)
    assert np.max(np.abs(got - z1 * z2)) < atol


def test_scheme_ops_bit_exact_2_10():
    """Acceptance pin, CG ring: multiply/rescale/rotate/conjugate through
    the jitted EvalPlan programs == the pre-refactor compositions, bit
    for bit."""
    # levels=1 keeps the host-oracle side cheap in tier-1 while still
    # exercising a multi-digit keyswitch (k=2) at the CG ring size
    _pin_scheme_ops(CkksContext(n=1 << 10, levels=1, scale_bits=28, seed=5), r=3)


@pytest.mark.slow  # ~45 s: host-oracle keyswitch at the paper's 2^14 ring
def test_scheme_ops_bit_exact_2_14():
    """Acceptance pin, four-step ring: same ops, natural-order NTT rows,
    every transform through the large-N banks pipeline."""
    # keyswitch noise grows with n and the digit count, and the rescaled
    # scale is ~2^26: loosen the decode bound (a convention bug is O(1))
    _pin_scheme_ops(CkksContext(n=1 << 14, levels=1, scale_bits=28, seed=6),
                    r=7, atol=1e-2)


@pytest.mark.slow  # interpret-mode kernels: ~12 s regardless of ring size
def test_plan_pallas_equals_ref():
    """The full jitted scheme programs on the Pallas kernel path
    (interpret mode) == the vmap reference path, end to end.  (Tier-1
    keeps the per-kernel pallas==ref pins: test_keyswitch_banks for the
    fused keyswitch, test_galois_banks_pallas_equals_ref for the gather,
    test_mod_down_banks_matches_host_oracle for the RNS floor.)"""
    ctx = CkksContext(n=64, levels=1, scale_bits=26, seed=8)
    rng = np.random.default_rng(9)
    z = rng.uniform(-1, 1, ctx.slots)
    ct = ctx.encrypt(ctx.encode(z))
    ref_plan = EvalPlan(ctx, use_pallas=False)
    pal_plan = EvalPlan(ctx, use_pallas=True)
    # multiply covers dyadic + fused keyswitch kernels, rotate adds the
    # galois gather; rescale's mod_down is pinned pallas-vs-ref in
    # test_mod_down_banks_matches_host_oracle and conjugate is the same
    # program as rotate (interpret mode is slow — keep this lean)
    for op in (lambda p: p.multiply(ct, ct),
               lambda p: p.rotate(ct, 2)):
        assert _ct_equal(op(ref_plan), op(pal_plan))


# ------------------------------------------------- galois_banks kernel

def test_galois_banks_pallas_equals_ref():
    """The Galois gather is one XLA gather on every backend (no Pallas
    kernel): shared and per-batch rows == the numpy gather."""
    n, k = 256, 3
    primes = gen_ntt_primes(k, n, bits=30)
    x = np.stack([RNG.integers(0, q, (5, n), dtype=np.uint32) for q in primes])
    idx = galois_eval_perm(5, n, False)
    got = np.asarray(ops.galois_banks(jnp.asarray(x), idx))
    assert np.array_equal(got, x[:, :, idx])
    rows = np.stack([galois_eval_perm(g, n, False) for g in (5, 25, 7, 5, 3)])
    got = np.asarray(ops.galois_banks(jnp.asarray(x), rows))
    assert np.array_equal(got, np.take_along_axis(x, rows[None], axis=-1))


@pytest.mark.parametrize("n,natural", [(1 << 10, False), (1 << 13, True)])
def test_eval_gather_matches_galois_poly(n, natural):
    """The NTT-domain gather (one galois_banks dispatch) == the
    coefficient-domain iNTT -> permute -> NTT oracle, for both frequency
    order conventions (bitrev CG rows and natural four-step rows)."""
    assert natural == (n >= ops.FOURSTEP_MIN_N)
    primes = tuple(gen_ntt_primes(2, n, bits=30))
    coeffs = RNG.integers(-(1 << 20), 1 << 20, size=n).astype(np.int64)
    p = rns.from_int_coeffs(coeffs, primes, n).to_ntt()
    for g in (5, pow(5, 11, 2 * n), 2 * n - 1):
        idx = galois_eval_perm(g, n, natural)
        got = p.automorphism(idx)
        want = galois_poly(p, g)
        assert np.array_equal(np.asarray(got.data), np.asarray(want.data)), g


# ------------------------------------------------------ mod_down_banks

def test_mod_down_banks_matches_host_oracle():
    """The extracted fused RNS floor == mod_down_by_last per polynomial,
    for both the keyswitch (drop special) and rescale (drop q_l) uses."""
    n = 64
    full = tuple(gen_ntt_primes(4, n, bits=30))
    t = FB.build_table_pack(list(full), n)
    x = np.stack([RNG.integers(0, q, (2, n), dtype=np.uint32) for q in full])
    for use_pallas in (False, True):
        got = np.asarray(FB.mod_down_banks(jnp.asarray(x), t,
                                           use_pallas=use_pallas))
        for b in range(2):
            want = mod_down_by_last(RnsPoly(jnp.asarray(x[:, b]), full, True))
            assert np.array_equal(got[:, b], np.asarray(want.data)), (use_pallas, b)


# ------------------------------------------- vectorized host satellites

def test_galois_int_coeffs_matches_loop_oracle():
    n = 128
    coeffs = RNG.integers(-50, 50, n).astype(np.int64)
    for g in (5, 25, 2 * n - 1):
        out = np.zeros(n, dtype=np.int64)     # the pre-refactor loop
        for t in range(n):
            u = (g * t) % (2 * n)
            if u < n:
                out[u] += coeffs[t]
            else:
                out[u - n] -= coeffs[t]
        assert np.array_equal(galois_int_coeffs(coeffs, g, n), out), g
        src, pos = galois_coeff_tables(g, n)
        assert sorted(src) == list(range(n))   # a permutation


def test_centered_to_float_paths():
    scale = float(1 << 28)
    small = np.array([0, 1, -1, 1 << 52, -(1 << 52)], dtype=object)
    got = rns.centered_to_float(small, scale)
    want = np.array([float(x) for x in small]) / scale
    assert np.array_equal(got, want)
    # past float64 range: the mantissa-shift fallback (2^1040 overflows
    # the direct cast; divided by 2^28 it fits again)
    huge = np.array([(1 << 1040) + 12345, -(1 << 1040)], dtype=object)
    got = rns.centered_to_float(huge, scale)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, [2.0 ** 1012, -(2.0 ** 1012)], rtol=1e-12)
    # non-integral scale (post-rescale CKKS scales are scale^2/q_l) stays
    # exact on the fallback path — no rounded-integer-divisor bias
    frac_scale = 2.0 ** 1000 / 3.0
    got = rns.centered_to_float(np.array([1 << 1040], dtype=object), frac_scale)
    np.testing.assert_allclose(got, [2.0 ** 40 * 3.0], rtol=1e-12)
    # truly unrepresentable magnitudes saturate to +-inf instead of raising
    got = rns.centered_to_float(np.array([1 << 1100, -(1 << 1100)], dtype=object),
                                scale)
    assert got[0] == np.inf and got[1] == -np.inf


def test_decode_matches_loop_decode():
    ctx = CkksContext(n=64, levels=3, scale_bits=28, seed=11)
    z = np.linspace(-1, 1, ctx.slots) + 1j * np.linspace(1, -1, ctx.slots)
    pt = ctx.encode(z)
    big = rns.crt_reconstruct_centered(pt.to_coeff())
    cf = np.array([float(x) for x in big]) / ctx.scale   # pre-refactor loop
    want = ctx._decode_coeffs(cf)
    got = ctx.decode(pt, ctx.scale)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(got, z, atol=1e-5)


# ------------------------------------------------------- plan caching

def test_plan_caches_and_prepare():
    ctx = CkksContext(n=128, levels=1, scale_bits=26, seed=12)
    plan = ctx.plan()
    assert ctx.plan() is plan                       # one plan per context
    basis = ctx.qs
    plan.prepare(rotations=(1,), conjugate=True)
    eb, ea = plan.relin_key(basis)
    assert eb.shape == (len(basis), len(basis) + 1, ctx.n)
    # prepared keys are returned by identity (no rebuild per op)
    assert plan.relin_key(basis)[0] is eb
    g = plan.rotation_group_element(1)
    gk = plan.galois_key(g, basis)
    assert plan.galois_key(g, basis)[0] is gk[0]
    assert plan.eval_idx(g) is plan.eval_idx(g)
