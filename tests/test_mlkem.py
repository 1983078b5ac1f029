"""ML-KEM-768 over the scheme-generic banks kernels (tier-1).

Three oracle layers, none sharing code with the implementation under
test:

* checked-in KAT vectors (``tests/vectors/mlkem768_kat.json``,
  generated by the pure-Python FIPS 203 transcription in
  ``mlkem_spec.py``) pin the serialized ek/dk/ct/K byte-for-byte,
* ``mlkem_spec`` itself cross-validates fresh random seeds and the
  FIPS-order NTT correspondence of the CG network,
* hypothesis-style sweeps (via ``hypcompat``) cover round-trip,
  linearity, and the negacyclic schoolbook product for the incomplete
  n=256/q=3329 transform, plus the lazy band edges of the 16-bit
  modmath helpers against the ``bits=16`` NumPy oracles.
"""
import json
import os

import numpy as np
import jax.numpy as jnp
import pytest

from hypcompat import given, settings, st
import mlkem_spec as spec

from repro.core import modmath as mm
from repro.core.ringspec import MLKEM_RING, RingSpec, ring_table_pack
from repro.kernels import ops
from repro.pq import mlkem

Q = mlkem.Q
N = mlkem.N
RNG = np.random.default_rng(41)
KAT_PATH = os.path.join(os.path.dirname(__file__), "vectors",
                        "mlkem768_kat.json")


def _kat():
    with open(KAT_PATH) as f:
        return json.load(f)["vectors"]


def _rows(cnt, lo=0, hi=Q):
    return RNG.integers(lo, hi, (cnt, N), dtype=np.uint16)


# ------------------------------------------------------------------ KATs

def test_keygen_matches_kat():
    """Batched keygen reproduces every checked-in ek/dk byte-for-byte."""
    vs = _kat()
    d = np.stack([np.frombuffer(bytes.fromhex(v["d"]), np.uint8) for v in vs])
    z = np.stack([np.frombuffer(bytes.fromhex(v["z"]), np.uint8) for v in vs])
    ek, dk = mlkem.keygen_batch(d, z)
    for i, v in enumerate(vs):
        assert bytes(ek[i]) == bytes.fromhex(v["ek"]), f"ek vector {i}"
        assert bytes(dk[i]) == bytes.fromhex(v["dk"]), f"dk vector {i}"


def test_encaps_matches_kat():
    vs = _kat()
    ek = np.stack([np.frombuffer(bytes.fromhex(v["ek"]), np.uint8) for v in vs])
    m = np.stack([np.frombuffer(bytes.fromhex(v["m"]), np.uint8) for v in vs])
    key, ct = mlkem.encaps_batch(ek, m)
    for i, v in enumerate(vs):
        assert bytes(key[i]) == bytes.fromhex(v["K"]), f"K vector {i}"
        assert bytes(ct[i]) == bytes.fromhex(v["ct"]), f"ct vector {i}"


def test_decaps_matches_kat_and_implicit_rejection():
    """Honest ciphertexts recover K; a flipped bit lands on the pinned
    implicit-rejection key (J(z‖ct)), not an error."""
    vs = _kat()
    dk = np.stack([np.frombuffer(bytes.fromhex(v["dk"]), np.uint8) for v in vs])
    ct = np.stack([np.frombuffer(bytes.fromhex(v["ct"]), np.uint8) for v in vs])
    key = mlkem.decaps_batch(dk, ct)
    for i, v in enumerate(vs):
        assert bytes(key[i]) == bytes.fromhex(v["K"]), f"K vector {i}"
    bad = ct.copy()
    bad[:, 17] ^= 0x01
    rej = mlkem.decaps_batch(dk, bad)
    for i, v in enumerate(vs):
        assert bytes(rej[i]) == bytes.fromhex(
            v["K_reject_flip_ct_byte17_bit0"]), f"reject vector {i}"


def test_random_seeds_match_spec_oracle():
    """Fresh seeds through the batched kernel path == the pure-Python
    FIPS 203 transcription, end to end."""
    B = 3
    d = RNG.integers(0, 256, (B, 32), dtype=np.uint8)
    z = RNG.integers(0, 256, (B, 32), dtype=np.uint8)
    m = RNG.integers(0, 256, (B, 32), dtype=np.uint8)
    ek, dk = mlkem.keygen_batch(d, z)
    key, ct = mlkem.encaps_batch(ek, m)
    back = mlkem.decaps_batch(dk, ct)
    for i in range(B):
        ek_s, dk_s = spec.keygen(bytes(d[i]), bytes(z[i]))
        assert bytes(ek[i]) == ek_s and bytes(dk[i]) == dk_s
        k_s, ct_s = spec.encaps(ek_s, bytes(m[i]))
        assert bytes(key[i]) == k_s and bytes(ct[i]) == ct_s
        assert bytes(back[i]) == k_s


# --------------------------------------- incomplete n=256/q=3329 transform

def test_cg_ntt_matches_fips_ntt_order():
    """The CG-network incomplete NTT is the FIPS 203 NTT up to the fixed
    pair permutation applied at serialization boundaries."""
    x = _rows(5)
    got = mlkem._ntt_rows(x)[..., mlkem._TO_FIPS]
    for i in range(len(x)):
        assert got[i].tolist() == spec.ntt(x[i].tolist()), f"row {i}"


def test_cg_basemul_matches_fips_basemul():
    a, b = _rows(4), _rows(4)
    got = mlkem._basemul_rows(a, b)[..., mlkem._TO_FIPS]
    for i in range(len(a)):
        want = spec.basemul(a[i][mlkem._TO_FIPS].tolist(),
                            b[i][mlkem._TO_FIPS].tolist())
        assert got[i].tolist() == want, f"row {i}"


@settings(max_examples=10)
@given(seed=st.integers(0, 2**32 - 1))
def test_ntt_roundtrip_and_linearity(seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, Q, (2, N), dtype=np.uint16)
    b = rng.integers(0, Q, (2, N), dtype=np.uint16)
    ah, bh = mlkem._ntt_rows(a), mlkem._ntt_rows(b)
    assert np.array_equal(mlkem._intt_rows(ah), a)
    lin = mlkem._ntt_rows(((a.astype(np.int64) + b) % Q).astype(np.uint16))
    assert np.array_equal(lin, ((ah.astype(np.int64) + bh) % Q))


@settings(max_examples=10)
@given(seed=st.integers(0, 2**32 - 1))
def test_basemul_equals_negacyclic_schoolbook(seed):
    """intt(ntt(a) ⊛ ntt(b)) == a·b mod (x^256 + 1, q): the incomplete
    transform + degree-1 basecase IS negacyclic polynomial
    multiplication."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, Q, N, dtype=np.uint16)
    b = rng.integers(0, Q, N, dtype=np.uint16)
    got = mlkem._intt_rows(mlkem._basemul_rows(
        mlkem._ntt_rows(a[None]), mlkem._ntt_rows(b[None])))[0]
    full = np.convolve(a.astype(np.int64), b.astype(np.int64))
    want = (full[:N] - np.concatenate([full[N:], [0]])) % Q
    assert np.array_equal(got.astype(np.int64), want)


def test_u16_banks_pallas_equals_ref():
    """Interpret-mode Pallas kernels == the vmap reference on the u16
    incomplete ring, for fwd/inv NTT and the basemul kernel."""
    t = ring_table_pack(MLKEM_RING)
    x = _rows(5)[None]          # (k=1, 5, 256), odd batch exercises padding
    a, b = _rows(5)[None], _rows(5)[None]
    fp = np.asarray(ops.ntt_banks(jnp.asarray(x), t, negacyclic=False,
                                  use_pallas=True))
    fr = np.asarray(ops.ntt_banks(jnp.asarray(x), t, negacyclic=False,
                                  use_pallas=False))
    assert fp.dtype == np.uint16 and np.array_equal(fp, fr)
    ip = np.asarray(ops.intt_banks(jnp.asarray(x), t, negacyclic=False,
                                   use_pallas=True))
    ir = np.asarray(ops.intt_banks(jnp.asarray(x), t, negacyclic=False,
                                   use_pallas=False))
    assert np.array_equal(ip, ir)
    for lazy in (False, True):
        bp = np.asarray(ops.dyadic_basemul_banks(
            jnp.asarray(a), jnp.asarray(b), t, use_pallas=True, lazy=lazy))
        br = np.asarray(ops.dyadic_basemul_banks(
            jnp.asarray(a), jnp.asarray(b), t, use_pallas=False, lazy=lazy))
        assert bp.dtype == np.uint16 and np.array_equal(bp, br), f"{lazy=}"


def test_u16_ring_on_u32_lanes_matches_u16_rows():
    """The ML-KEM host path hands the kernels u32 rows of the u16 ring:
    every banks entry point returns the same values as on u16 rows, in
    the caller's u32 dtype, on both dispatch paths."""
    t = ring_table_pack(MLKEM_RING)
    x16, y16 = _rows(9)[None], _rows(9)[None]
    x32, y32 = x16.astype(np.uint32), y16.astype(np.uint32)
    for up in (False, True):
        fns = [lambda v, w: ops.ntt_banks(v, t, negacyclic=False,
                                          use_pallas=up),
               lambda v, w: ops.intt_banks(v, t, negacyclic=False,
                                           use_pallas=up),
               lambda v, w: ops.dyadic_basemul_banks(v, w, t, use_pallas=up)]
        for fn in fns:
            got = np.asarray(fn(jnp.asarray(x32), jnp.asarray(y32)))
            want = np.asarray(fn(jnp.asarray(x16), jnp.asarray(y16)))
            assert got.dtype == np.uint32 and np.array_equal(got, want), up


# --------------------------------------------- 16-bit modmath lane contract

_EDGES = np.array([0, 1, Q - 1, Q, 2 * Q - 1], dtype=np.uint16)


def test_u16_shoup_band_edges():
    """u16 Shoup lazy/strict vs the bits=16 NumPy oracle at the lazy
    band edges {0, 1, q-1, q, 2q-1} (x) × edge and random w < q."""
    ws = np.array([0, 1, Q - 1, 17, 1729], dtype=np.uint16)
    x, w = [a.reshape(-1) for a in np.meshgrid(_EDGES, ws)]
    wp = np.array([mm.shoup_precompute(int(wi), Q, bits=16) for wi in w],
                  dtype=np.uint16)
    q16 = jnp.uint16(Q)
    lazy = np.asarray(mm.mulmod_shoup_lazy(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(wp), q16))
    want = mm.mulmod_shoup_lazy_np(x.astype(np.uint64), w.astype(np.uint64),
                                   Q, bits=16)
    assert lazy.dtype == np.uint16
    assert np.array_equal(lazy.astype(np.uint64), want)
    assert int(lazy.max()) < 2 * Q                      # lazy band holds
    strict = np.asarray(mm.mulmod_shoup(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(wp), q16))
    assert np.array_equal(
        strict.astype(np.int64),
        x.astype(np.int64) * w.astype(np.int64) % Q)


def test_u16_barrett_band_edges():
    """u16 Barrett lazy/strict vs the bits=16 NumPy oracle over all
    pairs of lazy band edges (inputs up to 2q-1, as after a lazy NTT)."""
    mu = mm.barrett_precompute(Q, bits=16)
    a, b = [v.reshape(-1) for v in np.meshgrid(_EDGES, _EDGES)]
    q16 = jnp.uint16(Q)
    lazy = np.asarray(mm.mulmod_barrett_lazy(
        jnp.asarray(a), jnp.asarray(b), q16, jnp.uint16(mu)))
    want = mm.mulmod_barrett_lazy_np(a.astype(np.uint64),
                                     b.astype(np.uint64), Q, bits=16)
    assert lazy.dtype == np.uint16
    assert np.array_equal(lazy.astype(np.uint64), want)
    assert int(lazy.max()) < 2 * Q
    strict = np.asarray(mm.mulmod_barrett(
        jnp.asarray(a), jnp.asarray(b), q16, jnp.uint16(mu)))
    assert np.array_equal(
        strict.astype(np.int64),
        a.astype(np.int64) * b.astype(np.int64) % Q)


@settings(max_examples=25)
@given(x=st.integers(0, 2 * Q - 1), w=st.integers(0, Q - 1))
def test_u16_helpers_random_sweep(x, w):
    wp = mm.shoup_precompute(w, Q, bits=16)
    mu = mm.barrett_precompute(Q, bits=16)
    q16 = jnp.uint16(Q)
    s = int(np.asarray(mm.mulmod_shoup(
        jnp.uint16(x), jnp.uint16(w), jnp.uint16(wp), q16)))
    assert s == x * w % Q
    r = int(np.asarray(mm.mulmod_barrett_lazy(
        jnp.uint16(x), jnp.uint16(w), q16, jnp.uint16(mu))))
    assert r < 2 * Q and r % Q == x * w % Q


# ------------------------------------------------------------ ring guards

def test_ringspec_rejects_bad_moduli():
    with pytest.raises(ValueError, match="3328"):
        RingSpec(name="bad", n=256, q=3328, dtype="uint16", block=2)
    with pytest.raises(ValueError, match="uint16"):
        # prime, NTT-friendly for order 256, but over the 16-bit window
        RingSpec(name="bad", n=256, q=7681, dtype="uint16", block=2)
    with pytest.raises(ValueError):
        RingSpec(name="bad", n=256, q=3329, dtype="uint16", block=3)
    with pytest.raises(ValueError):
        RingSpec(name="bad", n=255, q=3329, dtype="uint16", block=2)
