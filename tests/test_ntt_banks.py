"""Conformance of the fused multi-prime (prime, batch_tile) NTT banks.

Oracle chain: the Pallas banks kernel (interpret mode on CPU) and the
vmap reference path are both checked directly against the O(n^2) NumPy
golden model per prime row — no intermediate oracle."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core.ntt import brute_ntt_bitrev_np
from repro.core.params import gen_ntt_primes, make_ntt_params
from repro.fhe import batched as FB
from repro.fhe import rns
from repro.kernels import ntt_kernel, ops

RNG = np.random.default_rng(31)


def _pack(n, count=3):
    primes = gen_ntt_primes(count, n, bits=30)
    return primes, FB.build_table_pack(primes, n)


def _stack_rand(primes, batch, n):
    return np.stack([RNG.integers(0, q, (batch, n), dtype=np.uint32)
                     for q in primes])


@pytest.mark.parametrize("n", [128, 256])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_fwd_banks_vs_golden_model(n, use_pallas):
    """Every prime row of the fused (prime, batch) grid == brute-force
    eq.(1) in bit-reversed order (paper §VII.C golden model)."""
    primes, t = _pack(n)
    x = _stack_rand(primes, 4, n)
    got = np.asarray(ops.ntt_banks(jnp.asarray(x), t, negacyclic=False,
                                   use_pallas=use_pallas))
    for i, q in enumerate(primes):
        want = brute_ntt_bitrev_np(x[i], make_ntt_params(n, q=q).omega, q)
        assert np.array_equal(got[i], want), f"prime row {i}"


@pytest.mark.parametrize("n", [128, 256])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_banks_negacyclic_roundtrip(n, use_pallas):
    """(The cyclic direction is pinned by the golden-model test above
    and the pallas==ref cross-check below.)"""
    primes, t = _pack(n)
    x = _stack_rand(primes, 5, n)
    y = ops.ntt_banks(jnp.asarray(x), t, negacyclic=True,
                      use_pallas=use_pallas)
    back = np.asarray(ops.intt_banks(y, t, negacyclic=True,
                                     use_pallas=use_pallas))
    assert np.array_equal(back, x)


@pytest.mark.parametrize("n", [128])
def test_banks_pallas_equals_ref(n):
    """The fused kernel and the vmap reference are the same function."""
    primes, t = _pack(n, count=4)
    x = jnp.asarray(_stack_rand(primes, 3, n))
    for negacyclic in (False, True):
        a = np.asarray(ops.ntt_banks(x, t, negacyclic=negacyclic, use_pallas=True))
        b = np.asarray(ops.ntt_banks(x, t, negacyclic=negacyclic, use_pallas=False))
        assert np.array_equal(a, b)
        ia = np.asarray(ops.intt_banks(x, t, negacyclic=negacyclic, use_pallas=True))
        ib = np.asarray(ops.intt_banks(x, t, negacyclic=negacyclic, use_pallas=False))
        assert np.array_equal(ia, ib)


@pytest.mark.parametrize("n", [128, 256])
def test_rnspoly_stacked_roundtrip(n):
    """intt(ntt(x)) == x through the stacked RnsPoly (negacyclic)."""
    primes = tuple(gen_ntt_primes(3, n, bits=30))
    coeffs = RNG.integers(-(1 << 20), 1 << 20, size=n).astype(np.int64)
    p = rns.from_int_coeffs(coeffs, primes, n)
    back = p.to_ntt().to_coeff()
    assert np.array_equal(np.asarray(back.data), np.asarray(p.data))
    # and the centered CRT reconstruction recovers the original integers
    rec = rns.crt_reconstruct_centered(back)
    assert np.array_equal(rec.astype(np.int64), coeffs)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_dyadic_inner_banks(use_pallas):
    """Fused digit inner product == u64 NumPy oracle."""
    n, d, B = 128, 3, 5
    primes, t = _pack(n, count=3)
    k = len(primes)
    ext = np.stack([_stack_rand(primes, B, n) for _ in range(d)])
    evk = np.stack([np.stack([RNG.integers(0, q, (n,), dtype=np.uint32)
                              for q in primes]) for _ in range(d)])
    got = np.asarray(ops.dyadic_inner_banks(jnp.asarray(ext), jnp.asarray(evk),
                                            t, use_pallas=use_pallas))
    for j, q in enumerate(primes):
        acc = np.zeros((B, n), dtype=np.uint64)
        for i in range(d):
            acc = (acc + ext[i, j].astype(np.uint64)
                   * evk[i, j].astype(np.uint64) % q) % q
        assert np.array_equal(got[j], acc.astype(np.uint32))


def test_banks_odd_batch_padding():
    """Batch sizes that are not tile multiples pad/unpad transparently."""
    n = 128
    primes, t = _pack(n)
    x = _stack_rand(primes, 3, n)       # 3 % tile(8) != 0
    a = np.asarray(ops.ntt_banks(jnp.asarray(x), t, use_pallas=True))
    b = np.asarray(ops.ntt_banks(jnp.asarray(x), t, use_pallas=False))
    assert np.array_equal(a, b)


# ------------------------------------------------ kernel block geometry

def _mlkem_pack():
    from repro.core.ringspec import MLKEM_RING, ring_table_pack
    return (MLKEM_RING.q,), ring_table_pack(MLKEM_RING)


# (n, primes, batch): several grid steps per prime, each a stack of many
# slabs (2,048 rows at n=128 -> four 512-row blocks of 64 slabs); a
# padded batch that is no multiple of the budget's rows (24*128 + 8 =
# 3,080 -> seven 440-row blocks); ML-KEM's bits=16 ring at b=96 (one
# block of 12 slabs).
GEOMETRY_CASES = {
    "multi_step": (128, 2, 2048),
    "odd_batch": (128, 2, 24 * 128 + 8),
    "mlkem_b96": (256, None, 96),
}


@pytest.mark.parametrize("reduce_out", [False, True],
                         ids=["lazy_raw", "lazy_reduced"])
@pytest.mark.parametrize("inverse", [False, True], ids=["fwd", "inv"])
@pytest.mark.parametrize("case", list(GEOMETRY_CASES))
def test_banks_geometry_pallas_equals_ref(case, inverse, reduce_out):
    """Blocks of many rows, run as stacks of slabs, give the reference's
    values bit for bit, lazy [0, 2q) representatives included."""
    n, count, batch = GEOMETRY_CASES[case]
    if count is None:
        primes, t = _mlkem_pack()
        negacyclic = False              # as pq.mlkem calls the banks
    else:
        primes, t = _pack(n, count)
        negacyclic = True
    x = jnp.asarray(_stack_rand(primes, batch, n))
    fn = ops.intt_banks if inverse else ops.ntt_banks
    got, want = (np.asarray(fn(x, t, negacyclic=negacyclic, lazy=True,
                               reduce_out=reduce_out, use_pallas=p))
                 for p in (True, False))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("n", [128, 256, 1024, 4096])
@pytest.mark.parametrize("b,tile", [(3, 3), (8, 8), (96, 8), (3080, 8),
                                    (4096, 8), (8192, 16), (32768, 8),
                                    (8 * 1031, 8)])
def test_block_rows_rule(b, tile, n):
    """Rows per step: the whole batch or a multiple of the tile, a
    divisor of the padded batch, within the byte budget (a tile at
    least)."""
    rows = ntt_kernel.block_rows(b, n, tile)
    assert b % rows == 0
    assert rows == b or rows % tile == 0
    assert rows * n * 4 <= ntt_kernel.BLOCK_BYTES or rows == tile


def test_block_rows_four_step_passes():
    """The 2^14 four-step passes of both CKKS cells get blocks of many
    vregs; the ML-KEM rings one whole-batch block."""
    for b in (32768, 8192, 7168, 4096, 2048):
        assert ntt_kernel.block_rows(b, 128, 8) == 512
    assert ntt_kernel.block_rows(96, 256, 8) == 96
    assert ntt_kernel.block_rows(32, 256, 8) == 32


def test_block_span_records_geometry():
    """Building a banks NTT program records its block geometry."""
    primes, t = _pack(128, 2)
    x = jnp.asarray(_stack_rand(primes, 1032, 128))   # 1032 = 8 * 129
    obs.enable(); obs.clear()
    try:
        ops.ntt_banks(x, t, use_pallas=True)
    finally:
        obs.disable()
    ev = [e for e in obs.events() if e["name"] == "kernels.ntt_banks.block"]
    assert [(e["args"]["k"], e["args"]["b"], e["args"]["n"],
             e["args"]["rows"], e["args"]["steps"]) for e in ev] == \
        [(2, 1032, 128, 344, 6)]
