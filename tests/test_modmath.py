import numpy as np
import jax.numpy as jnp
import pytest
from hypcompat import given, settings, st

from repro.core import modmath as mm
from repro.core.params import gen_ntt_primes, is_prime

Q = gen_ntt_primes(1, 128, bits=30)[0]
RNG = np.random.default_rng(42)


def _rand(n, hi=2**32):
    return RNG.integers(0, hi, size=n, dtype=np.uint32)


def test_is_prime_known():
    assert is_prime(2) and is_prime(97) and is_prime((1 << 31) - 1)
    assert not is_prime(1) and not is_prime(561) and not is_prime(2**30)


def test_mulhi_matches_numpy():
    a, b = _rand(4096), _rand(4096)
    got = np.asarray(mm.mulhi_u32(jnp.asarray(a), jnp.asarray(b)))
    assert np.array_equal(got, mm.mulhi_np(a, b))


def test_addsub_mod():
    a, b = _rand(4096, Q), _rand(4096, Q)
    qa = jnp.uint32(Q)
    assert np.array_equal(np.asarray(mm.addmod(jnp.asarray(a), jnp.asarray(b), qa)),
                          mm.addmod_np(a, b, Q))
    assert np.array_equal(np.asarray(mm.submod(jnp.asarray(a), jnp.asarray(b), qa)),
                          mm.submod_np(a, b, Q))


def test_shoup_mulmod():
    x = _rand(4096, Q)
    w = int(_rand(1, Q)[0])
    wp = mm.shoup_precompute(w, Q)
    got = np.asarray(mm.mulmod_shoup(jnp.asarray(x), jnp.uint32(w), jnp.uint32(wp), jnp.uint32(Q)))
    assert np.array_equal(got, mm.mulmod_np(x, w, Q))


def test_barrett_mulmod():
    mu = mm.barrett_precompute(Q)
    a, b = _rand(4096, Q), _rand(4096, Q)
    got = np.asarray(mm.mulmod_barrett(jnp.asarray(a), jnp.asarray(b), jnp.uint32(Q), jnp.uint32(mu)))
    assert np.array_equal(got, mm.mulmod_np(a, b, Q))


def test_montgomery_mulmod():
    qinv_neg, r2 = mm.montgomery_precompute(Q)
    a, b = _rand(4096, Q), _rand(4096, Q)
    got = np.asarray(mm.mulmod_montgomery(jnp.asarray(a), jnp.asarray(b), jnp.uint32(Q),
                                          jnp.uint32(qinv_neg), jnp.uint32(r2)))
    assert np.array_equal(got, mm.mulmod_np(a, b, Q))


@settings(max_examples=200)
@given(x=st.integers(0, 2**32 - 1), w=st.integers(0, Q - 1))
def test_shoup_property(x, w):
    """Shoup accepts ANY u32 x (lazy inputs), result fully reduced."""
    wp = mm.shoup_precompute(w, Q)
    got = int(mm.mulmod_shoup(jnp.uint32(x), jnp.uint32(w), jnp.uint32(wp), jnp.uint32(Q)))
    assert got == (x * w) % Q


@settings(max_examples=100)
@given(a=st.integers(0, Q - 1), b=st.integers(0, Q - 1))
def test_all_multipliers_agree(a, b):
    mu = mm.barrett_precompute(Q)
    qinv_neg, r2 = mm.montgomery_precompute(Q)
    want = (a * b) % Q
    assert int(mm.mulmod_barrett(jnp.uint32(a), jnp.uint32(b), jnp.uint32(Q), jnp.uint32(mu))) == want
    wp = mm.shoup_precompute(b, Q)
    assert int(mm.mulmod_shoup(jnp.uint32(a), jnp.uint32(b), jnp.uint32(wp), jnp.uint32(Q))) == want
    assert int(mm.mulmod_montgomery(jnp.uint32(a), jnp.uint32(b), jnp.uint32(Q),
                                    jnp.uint32(qinv_neg), jnp.uint32(r2))) == want


@pytest.mark.parametrize("bits", [29, 30])
def test_barrett_other_primes(bits):
    for q in gen_ntt_primes(3, 256, bits=bits):
        mu = mm.barrett_precompute(q)
        a, b = _rand(1024, q), _rand(1024, q)
        got = np.asarray(mm.mulmod_barrett(jnp.asarray(a), jnp.asarray(b), jnp.uint32(q), jnp.uint32(mu)))
        assert np.array_equal(got, mm.mulmod_np(a, b, q))


# ------------------------------------------------------- lazy reduction
#
# The lazy contract (values in [0, 2q) between stages): each helper must
# (a) stay inside its band, (b) stay congruent mod q, and (c) match its
# numpy oracle bit-for-bit INCLUDING the representative — the kernels
# hand unreduced values across stage boundaries, so the representative
# itself is part of the pinned behavior.

@settings(max_examples=200)
@given(a=st.integers(0, 2 * Q - 1), b=st.integers(0, 2 * Q - 1))
def test_lazy_addsub_property(a, b):
    ga = int(mm.lazy_addmod(jnp.uint32(a), jnp.uint32(b), jnp.uint32(Q)))
    gs = int(mm.lazy_submod(jnp.uint32(a), jnp.uint32(b), jnp.uint32(Q)))
    assert ga < 2 * Q and ga % Q == (a + b) % Q
    assert gs < 2 * Q and gs % Q == (a - b) % Q
    assert ga == int(mm.lazy_addmod_np(a, b, Q))
    assert gs == int(mm.lazy_submod_np(a, b, Q))


@settings(max_examples=200)
@given(x=st.integers(0, 2**32 - 1), w=st.integers(0, Q - 1))
def test_shoup_lazy_property(x, w):
    """mulmod_shoup_lazy accepts ANY u32 x and lands in [0, 2q)."""
    wp = mm.shoup_precompute(w, Q)
    got = int(mm.mulmod_shoup_lazy(jnp.uint32(x), jnp.uint32(w),
                                   jnp.uint32(wp), jnp.uint32(Q)))
    assert got < 2 * Q and got % Q == (x * w) % Q
    assert got == int(mm.mulmod_shoup_lazy_np(x, w, Q))


@settings(max_examples=200)
@given(a=st.integers(0, Q - 1), b=st.integers(0, Q - 1))
def test_barrett_lazy_property(a, b):
    mu = mm.barrett_precompute(Q)
    got = int(mm.mulmod_barrett_lazy(jnp.uint32(a), jnp.uint32(b),
                                     jnp.uint32(Q), jnp.uint32(mu)))
    assert got < 2 * Q and got % Q == (a * b) % Q
    assert got == int(mm.mulmod_barrett_lazy_np(a, b, Q))


def test_lazy_band_edges_exact():
    """All pairs over the {0, 1, q-1, q, q+1, 2q-1} boundary set — the
    exact band edges the hypothesis sweep may or may not hit."""
    edges = np.array([0, 1, Q - 1, Q, Q + 1, 2 * Q - 1], dtype=np.uint32)
    a = np.repeat(edges, len(edges))
    b = np.tile(edges, len(edges))
    qa = jnp.uint32(Q)
    ga = np.asarray(mm.lazy_addmod(jnp.asarray(a), jnp.asarray(b), qa))
    gs = np.asarray(mm.lazy_submod(jnp.asarray(a), jnp.asarray(b), qa))
    assert np.array_equal(ga, mm.lazy_addmod_np(a, b, Q))
    assert np.array_equal(gs, mm.lazy_submod_np(a, b, Q))
    assert ga.max() < 2 * Q and gs.max() < 2 * Q
    w = Q - 1
    wp = mm.shoup_precompute(w, Q)
    gm = np.asarray(mm.mulmod_shoup_lazy(jnp.asarray(a), jnp.uint32(w),
                                         jnp.uint32(wp), qa))
    assert np.array_equal(gm, mm.mulmod_shoup_lazy_np(a, w, Q))
    assert gm.max() < 2 * Q


def test_barrett_precompute_range_valueerror():
    """The 2^28 < q < 2^30 guard is a ValueError, not a bare assert."""
    for bad in (0, 1, 1 << 28, 1 << 30, (1 << 31) - 1):
        with pytest.raises(ValueError, match="barrett_precompute"):
            mm.barrett_precompute(bad)
    assert mm.barrett_precompute(Q) == (1 << 60) // Q


def test_barrett_precompute_16bit_window_valueerror():
    """The 16-bit lane has its own (2^10, 2^12) window; the error names
    the offending modulus and the accepted range."""
    for bad in (0, 1, 1 << 10, 1 << 12, 3329 << 4):
        with pytest.raises(ValueError, match=rf"q={bad}.*uint16"):
            mm.barrett_precompute(bad, bits=16)
    assert mm.barrett_precompute(3329, bits=16) == (1 << 26) // 3329
    # a q valid for one lane is NOT silently accepted by the other
    with pytest.raises(ValueError):
        mm.barrett_precompute(3329)             # u16-window q on u32 lane
    with pytest.raises(ValueError):
        mm.barrett_precompute(Q, bits=16)       # u32-window q on u16 lane


def _run_O_guard(code):
    """Run ``code`` in a real ``python -O`` subprocess (asserts stripped)
    and require the GUARDED sentinel — the PR 7 guard-test pattern."""
    import os
    import subprocess
    import sys
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), os.pardir, "src"))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-O", "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "GUARDED" in out.stdout and "UNGUARDED" not in out.stdout, \
        f"stdout={out.stdout}\nstderr={out.stderr}"


def test_barrett_precompute_guard_survives_python_O():
    """Under ``python -O`` an assert is stripped; the guard must not be."""
    _run_O_guard(
        "from repro.core.modmath import barrett_precompute\n"
        "try:\n"
        "    barrett_precompute(1 << 31)\n"
        "except ValueError:\n"
        "    print('GUARDED')\n"
        "else:\n"
        "    print('UNGUARDED')\n"
    )


def test_barrett_precompute_16bit_guard_survives_python_O():
    _run_O_guard(
        "from repro.core.modmath import barrett_precompute\n"
        "try:\n"
        "    barrett_precompute(1 << 13, bits=16)\n"
        "except ValueError:\n"
        "    print('GUARDED')\n"
        "else:\n"
        "    print('UNGUARDED')\n"
    )


def test_params_root_guard_survives_python_O():
    """make_ntt_params rejects a non-NTT-friendly modulus as a
    ValueError naming q even under ``-O``."""
    _run_O_guard(
        "from repro.core.params import make_ntt_params\n"
        "try:\n"
        "    make_ntt_params(128, q=(1 << 29) + 5)\n"
        "except ValueError as e:\n"
        "    assert 'q=' in str(e)\n"
        "    print('GUARDED')\n"
        "else:\n"
        "    print('UNGUARDED')\n"
    )


def test_ringspec_guard_survives_python_O():
    """RingSpec's modulus-window check is a ValueError, not an assert."""
    _run_O_guard(
        "from repro.core.ringspec import RingSpec\n"
        "try:\n"
        "    RingSpec(name='bad', n=256, q=7681, dtype='uint16', block=2)\n"
        "except ValueError as e:\n"
        "    assert 'q=7681' in str(e) and 'uint16' in str(e)\n"
        "    print('GUARDED')\n"
        "else:\n"
        "    print('UNGUARDED')\n"
    )
