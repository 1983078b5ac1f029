"""Lazy-reduction correctness across the kernel stack.

The tentpole contract: with the default ``reduce_out=True`` epilogue,
lazy butterflies produce outputs BIT-IDENTICAL to the eager path (and
hence to the numpy oracles the eager path is pinned against).  With
``reduce_out=False``, the Pallas kernels and the jnp reference mirror
the same op sequence, so even the [0, 2q) representatives match.

Also here: the single-prime tile-clamp regression (a 1-row input must
dispatch a 1-row grid, not an 8x zero-padded one) and the galois
gather on batches that are no tile multiple.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.params import gen_ntt_primes, make_ntt_params
from repro.fhe import batched as FB
from repro.fhe import rns
from repro.kernels import ntt_kernel, ops

N = 1 << 10
RNG = np.random.default_rng(0xBEEF)


def _rows(qs, shape):
    qs = np.asarray(qs)
    return np.stack([RNG.integers(0, int(q), size=shape, dtype=np.uint32)
                     for q in qs])


# ------------------------------------------------- lazy == eager == ref

@pytest.mark.parametrize("negacyclic", [True, False])
def test_single_prime_lazy_eager_bitexact(negacyclic):
    p = make_ntt_params(N)
    x = RNG.integers(0, p.q, size=(5, N), dtype=np.uint32)
    outs = {}
    for lazy in (False, True):
        for use_pallas in (False, True):
            outs[(lazy, use_pallas)] = np.asarray(
                ops.ntt(x, p, negacyclic=negacyclic, use_pallas=use_pallas,
                        lazy=lazy))
    base = outs[(False, False)]
    for key, got in outs.items():
        assert np.array_equal(got, base), key
    # and the inverse round-trips on the lazy kernel path
    back = ops.intt(outs[(True, True)], p, negacyclic=negacyclic,
                    use_pallas=True, lazy=True)
    assert np.array_equal(np.asarray(back), x)


def test_banks_lazy_eager_bitexact():
    k = 3
    t = FB.build_table_pack(gen_ntt_primes(k, N), N)
    x = _rows(t["qs"], (4, N))
    base = np.asarray(ops.ntt_banks(x, t, use_pallas=False, lazy=False))
    for lazy in (False, True):
        for use_pallas in (False, True):
            got = np.asarray(ops.ntt_banks(x, t, use_pallas=use_pallas,
                                           lazy=lazy))
            assert np.array_equal(got, base), (lazy, use_pallas)
    back = ops.intt_banks(base, t, use_pallas=True, lazy=True)
    assert np.array_equal(np.asarray(back), x)


def test_banks_reduce_out_false_representative_exact():
    """Unreduced handoff: pallas and ref agree on the exact [0, 2q)
    representatives, which stay congruent to the canonical output."""
    k = 2
    t = FB.build_table_pack(gen_ntt_primes(k, N), N)
    qs = np.asarray(t["qs"]).astype(np.uint64)
    x = _rows(t["qs"], (4, N))
    canon = np.asarray(ops.ntt_banks(x, t, use_pallas=False, lazy=False))
    lp = np.asarray(ops.ntt_banks(x, t, use_pallas=True, lazy=True,
                                  reduce_out=False))
    lr = np.asarray(ops.ntt_banks(x, t, use_pallas=False, lazy=True,
                                  reduce_out=False))
    assert np.array_equal(lp, lr)
    assert (lp < (2 * qs)[:, None, None]).all()
    assert np.array_equal(lp % qs[:, None, None], canon)
    # inverse, same contract
    ip = np.asarray(ops.intt_banks(canon, t, use_pallas=True, lazy=True,
                                   reduce_out=False))
    ir = np.asarray(ops.intt_banks(canon, t, use_pallas=False, lazy=True,
                                   reduce_out=False))
    ic = np.asarray(ops.intt_banks(canon, t, use_pallas=False, lazy=False))
    assert np.array_equal(ip, ir)
    assert (ip < (2 * qs)[:, None, None]).all()
    assert np.array_equal(ip % qs[:, None, None], ic)


def test_dyadic_lazy_eager_bitexact():
    p = make_ntt_params(N)
    a = RNG.integers(0, p.q, size=(3, N), dtype=np.uint32)
    b = RNG.integers(0, p.q, size=(3, N), dtype=np.uint32)
    acc = RNG.integers(0, p.q, size=(3, N), dtype=np.uint32)
    for fn, args in ((ops.dyadic_mul, (a, b)), (ops.dyadic_mac, (acc, a, b))):
        base = np.asarray(fn(*args, p, use_pallas=False, lazy=False))
        for lazy in (False, True):
            for use_pallas in (False, True):
                got = np.asarray(fn(*args, p, use_pallas=use_pallas, lazy=lazy))
                assert np.array_equal(got, base), (fn.__name__, lazy, use_pallas)


def test_dyadic_inner_banks_lazy_eager_bitexact():
    k, d, B = 2, 3, 4
    t = FB.build_table_pack(gen_ntt_primes(k, N), N)
    ext = np.stack([_rows(t["qs"], (B, N)) for _ in range(d)])
    evk = np.stack([_rows(t["qs"], (N,)) for _ in range(d)])
    base = np.asarray(ops.dyadic_inner_banks(ext, evk, t, use_pallas=False,
                                             lazy=False))
    for lazy in (False, True):
        for use_pallas in (False, True):
            got = np.asarray(ops.dyadic_inner_banks(
                ext, evk, t, use_pallas=use_pallas, lazy=lazy))
            assert np.array_equal(got, base), (lazy, use_pallas)


def test_keyswitch_lazy_eager_bitexact():
    """The full Fig 22 pipeline (decompose + inner product + mod-down)
    under lazy butterflies is bit-identical to the eager path."""
    primes = tuple(rns.make_primes(64, 4))
    basis = primes[:-1]
    k = len(basis)
    t = FB.build_table_pack(list(primes), 64)
    d2 = np.stack([RNG.integers(0, q, size=(2, 64), dtype=np.uint32)
                   for q in basis])
    evk_b = np.stack([_rows(primes, (64,)) for _ in range(k)])
    evk_a = np.stack([_rows(primes, (64,)) for _ in range(k)])
    base = FB.batched_keyswitch(jnp.asarray(d2), jnp.asarray(evk_b),
                                jnp.asarray(evk_a), t, use_pallas=False,
                                lazy=False)
    for lazy in (False, True):
        for use_pallas in (False, True):
            got = FB.batched_keyswitch(jnp.asarray(d2), jnp.asarray(evk_b),
                                       jnp.asarray(evk_a), t,
                                       use_pallas=use_pallas, lazy=lazy)
            for g, b in zip(got, base):
                assert np.array_equal(np.asarray(g), np.asarray(b)), \
                    (lazy, use_pallas)


@pytest.mark.slow
@pytest.mark.parametrize("negacyclic", [True, False])
def test_fourstep_lazy_eager_bitexact_2_14(negacyclic):
    """Four-step lazy composition at the paper's 2^14 ring: the [0, 2q)
    inter-pass handoff still lands bit-exact."""
    n = 1 << 14
    k = 2
    fp = FB.build_fourstep_pack(gen_ntt_primes(k, n), n)
    x = _rows(fp["qs"], (2, n))
    base = np.asarray(ops.ntt_fourstep_banks(x, fp, negacyclic=negacyclic,
                                             use_pallas=False, lazy=False))
    for lazy in (False, True):
        for use_pallas in (False, True):
            got = np.asarray(ops.ntt_fourstep_banks(
                x, fp, negacyclic=negacyclic, use_pallas=use_pallas, lazy=lazy))
            assert np.array_equal(got, base), (lazy, use_pallas)
    back = ops.intt_fourstep_banks(base, fp, negacyclic=negacyclic,
                                   use_pallas=True, lazy=True)
    assert np.array_equal(np.asarray(back), x)


@pytest.mark.slow
def test_keyswitch_lazy_eager_bitexact_2_14():
    n = 1 << 14
    primes = tuple(rns.make_primes(n, 3))
    basis = primes[:-1]
    k = len(basis)
    t = FB.build_scalar_pack(list(primes))
    fsp = FB.build_fourstep_pack(list(primes), n)
    d2 = np.stack([RNG.integers(0, q, size=(1, n), dtype=np.uint32)
                   for q in basis])
    evk_b = np.stack([_rows(primes, (n,)) for _ in range(k)])
    evk_a = np.stack([_rows(primes, (n,)) for _ in range(k)])
    outs = []
    for lazy in (False, True):
        outs.append(FB.batched_keyswitch(
            jnp.asarray(d2), jnp.asarray(evk_b), jnp.asarray(evk_a), t,
            fsp=fsp, use_pallas=True, lazy=lazy))
    for g, b in zip(outs[1], outs[0]):
        assert np.array_equal(np.asarray(g), np.asarray(b))


# --------------------------------------------- single-prime tile clamp

def test_single_prime_tile_clamps_to_batch(monkeypatch):
    """A 1-row input must dispatch a 1-row kernel grid (regression: the
    single-prime entry points used to zero-pad to tile=8 — 8x wasted
    butterfly rows per dispatch)."""
    p = make_ntt_params(256)
    seen = {}

    def fake_fwd(x2, *args, tile, **kw):
        seen["rows"], seen["tile"] = x2.shape[0], tile
        return jnp.zeros_like(x2)

    monkeypatch.setattr(ntt_kernel, "ntt_fwd_pallas", fake_fwd)
    x = RNG.integers(0, p.q, size=(1, 256), dtype=np.uint32)
    ops.ntt(x, p, use_pallas=True)
    assert seen == {"rows": 1, "tile": 1}

    # a 5-row input clamps an explicit tile=8 to 5 (no padding at all)
    x5 = RNG.integers(0, p.q, size=(5, 256), dtype=np.uint32)
    ops.ntt(x5, p, use_pallas=True, tile=8)
    assert seen == {"rows": 5, "tile": 5}


def test_dyadic_tile_clamps_to_batch(monkeypatch):
    from repro.kernels import dyadic_kernel
    p = make_ntt_params(256)
    seen = {}

    def fake_mul(a2, b2, *, tile, **kw):
        seen["rows"], seen["tile"] = a2.shape[0], tile
        return jnp.zeros_like(a2)

    monkeypatch.setattr(dyadic_kernel, "dyadic_mul", fake_mul)
    a = RNG.integers(0, p.q, size=(1, 256), dtype=np.uint32)
    ops.dyadic_mul(a, a, p, use_pallas=True)
    assert seen == {"rows": 1, "tile": 1}


# --------------------------------------------------- galois iota pads

def test_galois_pad_rows_are_identity_not_zero():
    """The gather pads nothing: a batch of 3 per-row gathers (no tile
    multiple) equals the numpy gather row for row — no lane-0 reads
    leak in from any pad row."""
    k, n, B = 2, 128, 3
    t = FB.build_table_pack(gen_ntt_primes(k, n), n)
    x = _rows(t["qs"], (B, n))
    shift = np.roll(np.arange(n, dtype=np.int32), 5)
    idx = np.stack([shift] * B)
    got = np.asarray(ops.galois_banks(x, idx))
    assert np.array_equal(got, np.take_along_axis(x, idx[None], axis=-1))


def test_galois_digits_pad_rows_are_identity_not_zero():
    k, n, d, B = 2, 128, 2, 3
    t = FB.build_table_pack(gen_ntt_primes(k, n), n)
    ext = np.stack([_rows(t["qs"], (B, n)) for _ in range(d)])
    shift = np.roll(np.arange(n, dtype=np.int32), 9)
    idx = np.stack([shift] * B)
    got = np.asarray(ops.galois_digits_banks(ext, idx))
    assert np.array_equal(got,
                          np.take_along_axis(ext, idx[None, None], axis=-1))
