"""Hoisted-rotation subsystem + encrypted slot linear algebra.

Pins, in dependency order:
  * ``decompose_banks`` CRT round-trip property (hypcompat sweep): the
    digit extensions recombine to the input on every basis row.
  * ``ops.galois_digits_banks`` == the numpy gather (odd batches too).
  * ``hoisted_rotations_banks`` (via ``EvalPlan.rotate_hoisted``) ==
    a loop of the PR 3 single-rotation ``galois_ks_banks`` programs,
    bit for bit, for R in {1, 4, 8} at the CG ring (2^10, tier-1) and
    the four-step ring (2^14, slow — natural-order path).
  * ``linalg.matvec`` vs the numpy slot oracle, including non-square
    and padded-diagonal shapes, plus ``rotate_sum`` and the
    basis-validity / layout ValueErrors.
  * plan dispatch counters: hoisting reuse is visible as
    key_switches - decomposes.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from conftest import ct_equal as _eq
from hypcompat import given, settings, st

from repro.core.params import galois_eval_perm, gen_ntt_primes
from repro.fhe import batched as FB
from repro.fhe import linalg
from repro.fhe.ckks import CkksContext
from repro.kernels import ops

RNG = np.random.default_rng(81)


# ------------------------------------------------- decompose_banks


@settings(max_examples=8)
@given(st.integers(2, 4), st.integers(1, 3), st.integers(0, 1 << 16))
def test_decompose_banks_crt_roundtrip(k, B, seed):
    """The hoisting primitive inverts: recombining the (k, k+1, B, n)
    digit extensions with the CRT interpolation coefficients T_i
    (T_i = (Q/q_i) * ((Q/q_i)^-1 mod q_i), so T_i == delta_ij mod q_j)
    returns the input NTT rows exactly on every basis prime row.  (The
    special row k is NOT exact — recombination there is only congruent
    mod Q, which is why mod-down subtracts and floors instead.)"""
    n = 128
    primes = gen_ntt_primes(k + 1, n, bits=30)
    t = FB.build_table_pack(primes, n)
    rng = np.random.default_rng(seed)
    d2 = np.stack([rng.integers(0, q, (B, n), dtype=np.uint32)
                   for q in primes[:k]])
    y = np.asarray(FB.decompose_banks(jnp.asarray(d2), t))
    assert y.shape == (k, k + 1, B, n)
    Q = 1
    for q in primes[:k]:
        Q *= q
    Ts = []
    for qi in primes[:k]:
        Qi = Q // qi
        Ts.append(Qi * pow(Qi % qi, -1, qi) % Q)
    for j, qj in enumerate(primes[:k]):
        acc = np.zeros((B, n), dtype=np.uint64)
        for i in range(k):
            acc = (acc + y[i, j].astype(np.uint64) * np.uint64(Ts[i] % qj)) \
                  % np.uint64(qj)
        assert np.array_equal(acc.astype(np.uint32), d2[j]), (j, qj)


def test_galois_digits_banks_pallas_equals_ref():
    """The digit gather (one XLA gather on every backend) == the numpy
    fancy-index oracle, for even and odd batch sizes."""
    n, d, k = 256, 3, 2
    primes = gen_ntt_primes(k, n, bits=30)
    gs = [5, 25, 2 * n - 1, 7, 11]
    for b in (4, 5):          # 4 = tile multiple (tile=4), 5 = pad path
        x = np.stack([np.stack([RNG.integers(0, q, (b, n), dtype=np.uint32)
                                for q in primes]) for _ in range(d)])
        idx = np.stack([galois_eval_perm(g, n, False) for g in gs[:b]])
        got = np.asarray(ops.galois_digits_banks(jnp.asarray(x),
                                                 jnp.asarray(idx)))
        assert np.array_equal(got, x[:, :, np.arange(b)[:, None], idx]), b


def test_galois_digits_banks_shared_mode():
    """Shared (decompose-once) mode: a (d, k, 1, n) digit stack against
    (R, n) gather rows — every row reads the ONE stack, equal to the
    per-rotation replication it replaces, for even and odd R."""
    n, d, k = 256, 3, 2
    primes = gen_ntt_primes(k, n, bits=30)
    gs = [5, 25, 2 * n - 1, 7, 11]
    x1 = np.stack([np.stack([RNG.integers(0, q, (1, n), dtype=np.uint32)
                             for q in primes]) for _ in range(d)])
    for R in (4, 5):          # tile multiple + pad path (tile=4)
        idx = np.stack([galois_eval_perm(g, n, False) for g in gs[:R]])
        got = np.asarray(ops.galois_digits_banks(jnp.asarray(x1),
                                                 jnp.asarray(idx)))
        assert got.shape == (d, k, R, n), R
        rep = np.broadcast_to(x1, (d, k, R, n))
        assert np.array_equal(got, rep[:, :, np.arange(R)[:, None], idx]), R


# ------------------------------------- hoisted == loop of galois_ks_banks


def _pin_hoisted(ctx, Rs=(1, 4, 8)):
    rng = np.random.default_rng(82)
    z = rng.uniform(-1, 1, ctx.slots) + 1j * rng.uniform(-1, 1, ctx.slots)
    ct = ctx.encrypt(ctx.encode(z))
    plan = ctx.plan()
    for R in Rs:
        rs = list(range(1, R + 1))
        got = plan.rotate_hoisted(ct, rs)
        want = [plan.rotate(ct, r) for r in rs]
        assert all(_eq(g, w) for g, w in zip(got, want)), f"R={R}"
    # identity short-circuit + repeated amounts ride the same dispatch
    rs = [0, 3, 3, 5]
    got = plan.rotate_hoisted(ct, rs)
    want = [plan.rotate(ct, r) for r in rs]
    assert all(_eq(g, w) for g, w in zip(got, want))


def test_hoisted_rotations_bit_exact_2_10():
    """Acceptance pin, CG ring (bitrev NTT rows): one hoisted dispatch
    == a loop of PR 3 ``galois_ks_banks`` rotations, bit for bit."""
    _pin_hoisted(CkksContext(n=1 << 10, levels=1, scale_bits=28, seed=83))


@pytest.mark.slow  # ~2 min: hoisted + galois program compiles at 2^14
def test_hoisted_rotations_bit_exact_2_14():
    """Acceptance pin, four-step ring: the same hoisted program with
    every transform on the large-N banks pipeline (natural-order rows)."""
    _pin_hoisted(CkksContext(n=1 << 14, levels=1, scale_bits=28, seed=84),
                 Rs=(4,))


def test_hoisted_counters_record_reuse():
    ctx = CkksContext(n=256, levels=1, scale_bits=26, seed=85)
    rng = np.random.default_rng(86)
    z = rng.uniform(-1, 1, ctx.slots)
    ct = ctx.encrypt(ctx.encode(z))
    plan = ctx.plan().reset_stats()
    plan.rotate_hoisted(ct, [1, 2, 3, 4])
    assert plan.stats == {"dispatches": 1, "key_switches": 4, "decomposes": 1}
    plan.rotate(ct, 1)
    assert plan.stats == {"dispatches": 2, "key_switches": 5, "decomposes": 2}
    plan.rotate_hoisted(ct, [0, 0])          # all-identity: no dispatch
    assert plan.stats["dispatches"] == 2


# ------------------------------------------------------ matvec oracle


def _check_matvec(ctx, d_in, d_out, n1=None, seed=87, atol=1e-2):
    rng = np.random.default_rng(seed)
    W = rng.uniform(-0.5, 0.5, (d_in, d_out))
    x = rng.uniform(-1, 1, d_in)
    M = linalg.PtMatrix.encode(ctx, W, n1=n1)
    ct = ctx.encrypt(linalg.encode_vector(ctx, x, d_out))
    out = linalg.matvec(ctx.plan(), M, ct)
    got = ctx.decrypt_decode(out).real[:d_out]
    np.testing.assert_allclose(got, x @ W, atol=atol)
    return M


def test_matvec_square_and_bsgs_split():
    ctx = CkksContext(n=256, levels=1, scale_bits=26, seed=88)
    M = _check_matvec(ctx, 8, 8)
    assert (M.n1, M.n2) == (3, 3)            # ceil(sqrt(8)) split rule
    assert M.baby_set == (0, 1, 2) and M.giant_set == (3, 6)
    # an explicit non-default split computes the same product
    _check_matvec(ctx, 8, 8, n1=4)
    _check_matvec(ctx, 8, 8, n1=1)           # degenerate: all giant steps
    _check_matvec(ctx, 8, 8, n1=8)           # degenerate: all baby steps


def test_matvec_non_square_and_padded_diagonals():
    """Wide, tall, and split-padded shapes: d_in not a multiple of n1
    leaves the last giant group short (padded diagonals of the n1*n2
    grid never materialize), and rectangular W exercises diagonals
    whose wraparound mixes rows."""
    ctx = CkksContext(n=256, levels=1, scale_bits=26, seed=89)
    _check_matvec(ctx, 8, 3, seed=90)        # wide (d_out < d_in)
    _check_matvec(ctx, 6, 10, seed=91)       # tall (d_out > d_in)
    M = _check_matvec(ctx, 5, 7, seed=92)    # 5 = 3 + 2: short last group
    assert (M.n1, M.n2) == (3, 2)
    assert sorted(M.diags) == [(0, 0), (0, 1), (0, 2), (1, 0), (1, 1)]
    assert len(M.diags) == 5                 # no padded-diagonal ghosts


def test_matvec_zero_diagonals_are_skipped():
    ctx = CkksContext(n=256, levels=1, scale_bits=26, seed=93)
    W = np.zeros((8, 8))
    W[0, 0] = 0.25                           # only diagonal r=0 nonzero
    M = linalg.PtMatrix.encode(ctx, W)
    assert set(M.diags) == {(0, 0)} and M.baby_set == (0,)
    rng = np.random.default_rng(94)
    x = rng.uniform(-1, 1, 8)
    ct = ctx.encrypt(linalg.encode_vector(ctx, x, 8))
    plan = ctx.plan().reset_stats()
    out = linalg.matvec(plan, M, ct)
    assert plan.stats["key_switches"] == 0   # identity baby, no giants
    got = ctx.decrypt_decode(out).real[:8]
    np.testing.assert_allclose(got, x @ W, atol=1e-2)


def test_matvec_validation_errors():
    ctx = CkksContext(n=128, levels=2, scale_bits=26, seed=95)
    rng = np.random.default_rng(96)
    W = rng.uniform(-1, 1, (4, 4))
    M = linalg.PtMatrix.encode(ctx, W)       # valid at the FULL basis only
    plan = ctx.plan()
    x = rng.uniform(-1, 1, 4)
    ct = ctx.encrypt(linalg.encode_vector(ctx, x, 4))
    dropped = plan.rescale(ctx.mul_plain(ct, ctx.encode(np.ones(ctx.slots))))
    with pytest.raises(ValueError, match="valid at exactly one basis"):
        linalg.matvec(plan, M, dropped)
    # ...and a pack encoded AT the dropped basis works there
    M2 = linalg.PtMatrix.encode(ctx, W, basis=dropped.primes)
    out = linalg.matvec(plan, M2, dropped)
    assert out.primes == dropped.primes
    with pytest.raises(ValueError, match="exceeds"):
        linalg.PtMatrix.encode(ctx, rng.uniform(-1, 1, (40, 40)))
    with pytest.raises(ValueError, match="exceeds"):
        linalg.encode_vector(ctx, np.ones(40), 40)
    with pytest.raises(ValueError, match="n1"):
        linalg.PtMatrix.encode(ctx, W, n1=9)
    with pytest.raises(ValueError, match="2-D"):
        linalg.PtMatrix.encode(ctx, np.ones(4))
    with pytest.raises(ValueError, match="no nonzero diagonals"):
        linalg.matvec(plan, linalg.PtMatrix.encode(ctx, np.zeros((4, 4))), ct)


def test_prepare_matvecs_pins_matvec_traces():
    """Regression: ``prepare(warm_jit=True, batch_sizes=...)`` used to
    leave the matvec composite's giant-step ``rotate_many`` and
    hoisted-set signatures cold, so the first matvec through a
    "prepared" serving plan paid XLA compilation inside its latency
    window.  ``prepare(matvecs=(M,))`` warms the WHOLE composite; the
    pin is on plan counters: a post-prepare matvec compiles ZERO fresh
    traces.  (n=512 is used by no other tier-1 test, so the prepare
    call really does all the compiling here.)"""
    ctx = CkksContext(n=512, levels=1, scale_bits=26, seed=99)
    rng = np.random.default_rng(100)
    W = rng.uniform(-0.5, 0.5, (8, 8))
    M = linalg.PtMatrix.encode(ctx, W)
    x = rng.uniform(-1, 1, 8)
    ct = ctx.encrypt(linalg.encode_vector(ctx, x, 8))
    plan = ctx.plan()
    plan.prepare(relin=False, matvecs=(M,))
    before = plan.trace_count()
    out = linalg.matvec(plan, M, ct)
    assert plan.trace_count() == before      # zero fresh XLA traces
    got = ctx.decrypt_decode(out).real[:8]
    np.testing.assert_allclose(got, x @ W, atol=1e-2)


# --------------------------------------------------------- rotate_sum


def test_rotate_sum_matches_slot_oracle():
    ctx = CkksContext(n=128, levels=1, scale_bits=26, seed=97)
    rng = np.random.default_rng(98)
    z = rng.uniform(-1, 1, ctx.slots)
    ct = ctx.encrypt(ctx.encode(z))
    plan = ctx.plan().reset_stats()
    out = linalg.rotate_sum(plan, ct, 8)
    assert plan.stats["key_switches"] == 3   # log2(8) sequential rotations
    got = ctx.decrypt_decode(out).real
    want = np.array([z[(np.arange(8) + s) % ctx.slots].sum()
                     for s in range(ctx.slots)])
    np.testing.assert_allclose(got, want, atol=1e-2)
    with pytest.raises(ValueError, match="power of two"):
        linalg.rotate_sum(plan, ct, 6)
    with pytest.raises(ValueError, match="slots"):
        linalg.rotate_sum(plan, ct, 2 * ctx.slots)
