"""Grouped-digit hybrid key switching (dnum digits of several primes,
alpha special primes) on the program's normal path, against the plain
reference ``bench/reference/ckks_ref.py`` (numpy, no shared code).

* The program, with its own keys, agrees with ``CkksRef`` bit for bit
  for multiply + relin, rotate, conjugate, rescale and hoisted rotations
  (against unhoisted), at the top level and after one rescale (digits
  cut to the basis), on the single-kernel ring (n = 2^10) and the
  four-step ring (n = 2^13), with (dnum, alpha) in {(2, 3), (3, 2)} over
  five limbs: groups [3, 2] and [2, 2, 1] at the top, [3, 1] and [2, 2]
  one level down.
* The base-conversion kernel agrees with its jnp reference and with a
  Python-integer oracle.
* ``dnum=None, special_primes=1`` keeps the single-prime chain, keys,
  tables and programs: the plain centred lift and a one-row mod-down,
  no conversion kernel.
* A lower basis takes the top-level key's rows.
* Meshes refuse grouped shapes.
"""
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat, obs
from repro.fhe import batched as FB
from repro.fhe import evalplan as EV
from repro.fhe import rns
from repro.fhe.ckks import CkksContext
from repro.kernels import base_conv_kernel, ops

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "bench", "reference", "ckks_ref.py")


def _load_ref():
    spec = importlib.util.spec_from_file_location("ckks_ref", _REF)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.CkksRef


CkksRef = _load_ref()
LEVELS = 4                                   # five limbs


def _keys(evk):
    return (np.stack([np.asarray(p[0].data) for p in evk]),
            np.stack([np.asarray(p[1].data) for p in evk]))


def _arr(ct):
    return np.asarray(ct.c0.data), np.asarray(ct.c1.data)


def _same(got, want):
    return all(np.array_equal(np.asarray(g), np.asarray(w))
               for g, w in zip(got, want))


SHAPES = [(1024, 2, 3), (1024, 3, 2), (8192, 2, 3), (8192, 3, 2)]


@pytest.fixture(scope="module", params=SHAPES,
                ids=[f"n{n}-dnum{d}-alpha{a}" for n, d, a in SHAPES])
def grouped(request):
    n, dnum, alpha = request.param
    ctx = CkksContext(n=n, levels=LEVELS, seed=11, dnum=dnum,
                      special_primes=alpha)
    ref = CkksRef(n, LEVELS, "natural" if n >= ops.FOURSTEP_MIN_N
                  else "bitrev", dnum=dnum, special_primes=alpha)
    rng = np.random.default_rng(5)
    enc = lambda: ctx.encrypt(ctx.encode(rng.uniform(-1, 1, ctx.slots)))
    plan = ctx.plan()
    top = (enc(), enc())
    low = tuple(plan.rescale(ct) for ct in top)
    return ctx, ref, plan, {"top": top, "rescaled": low}


def test_chain_matches_reference(grouped):
    ctx, ref, _, _ = grouped
    assert ctx.qs == ref.qs and ctx.specials == ref.specials
    assert [tuple(ctx.qs[i] for i in g) for g in ref.digits(ctx.qs)] \
        == ctx.digit_groups(ctx.qs)


@pytest.mark.parametrize("level", ["top", "rescaled"])
@pytest.mark.parametrize("op", ["multiply", "rotate", "conjugate",
                                "rescale", "hoisted"])
def test_grouped_ops_match_reference(grouped, op, level):
    ctx, ref, plan, cts = grouped
    a, b = cts[level]
    basis = a.primes
    if op == "multiply":
        want = ref.multiply(_arr(a), _arr(b), basis,
                            _keys(ctx.relin_keys(basis)))
        assert _same(_arr(plan.multiply(a, b)), want)
    elif op in ("rotate", "conjugate"):
        g = 5 if op == "rotate" else 2 * ctx.n - 1
        key = _keys(ctx.galois_keys(g, basis))
        got = plan.rotate(a, 1) if op == "rotate" else plan.conjugate(a)
        assert _same(_arr(got), ref.galois(_arr(a), basis, g, key))
        assert ref.key_noise(*key, ctx._s_coeffs, basis, g) < 64
    elif op == "rescale":
        got = plan.rescale(a)
        assert got.primes == basis[:-1]
        assert _same(_arr(got), ref.rescale(_arr(a), basis))
    else:
        rs = [1, 2, 3]
        hoisted = plan.rotate_hoisted(a, rs)
        for r, h in zip(rs, hoisted):
            assert _same(_arr(h), _arr(plan.rotate(a, r)))
        g = ref.rotation_element(3)
        want = ref.galois(_arr(a), basis, g, _keys(ctx.galois_keys(g, basis)))
        assert _same(_arr(hoisted[2]), want)


def test_relin_key_is_sound(grouped):
    ctx, ref, _, _ = grouped
    for basis in (ctx.qs, ctx.qs[:-1]):
        evk = _keys(ctx.relin_keys(basis))
        assert evk[0].shape == (len(ref.digits(basis)),
                                len(basis) + len(ctx.specials), ctx.n)
        assert ref.key_noise(*evk, ctx._s_coeffs, basis) < 64


# ------------------------------------------------------- the kernel

def _conv_case(d, m, t, B, n, seed):
    primes = rns.make_primes(n, d * m + t)
    groups = [tuple(primes[j * m:(j + 1) * m]) for j in range(d)]
    groups[-1] = groups[-1][:max(1, m - 1)]          # an uneven last group
    dst = tuple(primes[-t:])
    rng = np.random.default_rng(seed)
    x = np.zeros((d, m, B, n), np.uint32)
    for j, grp in enumerate(groups):
        for i, q in enumerate(grp):
            x[j, i] = rng.integers(0, q, (B, n), dtype=np.uint32)
    return groups, dst, x


@pytest.mark.parametrize("d,m,t,B,n", [(3, 3, 8, 3, 256), (1, 2, 5, 8, 128),
                                       (2, 4, 6, 10, 1024)])
def test_base_conv_kernel_matches_reference(d, m, t, B, n):
    groups, dst, x = _conv_case(d, m, t, B, n, seed=d * 100 + m)
    tab = FB.base_conv_tables(groups, dst)
    want = ops.base_conv_banks(jnp.asarray(x), tab, use_pallas=False)
    obs.clear()
    obs.enable()
    try:
        got = ops.base_conv_banks(jnp.asarray(x), tab, use_pallas=True)
    finally:
        obs.disable()
    assert got.shape == (t, d, B, n)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    spans = [e for e in obs.events() if e["name"] == "kernels.base_conv.block"]
    assert spans and spans[0]["args"]["m"] == m
    assert spans[0]["args"]["targets"] == t
    assert spans[0]["args"]["n"] == n
    # Python integers: sum_i [x_i (M/m_i)^-1]_{m_i, centred} (M/m_i) mod p
    for j, grp in enumerate(groups):
        M = math.prod(grp)
        for b, c in ((0, 0), (B - 1, n - 1), (B // 2, n // 3)):
            tot = 0
            for i, mi in enumerate(grp):
                y = int(x[j, i, b, c]) * pow(M // mi % mi, -1, mi) % mi
                tot += (y - mi if y > mi // 2 else y) * (M // mi)
            assert [int(v) for v in np.asarray(want)[:, j, b, c]] \
                == [tot % p for p in dst]


# ------------------------------------------ today's shape is unchanged

def _single_prime_relin_keys(ctx):
    """The single-prime-digit relin key written out from its
    definition, drawing from the context's generator in keygen's order:
    one digit per prime q_i, T_i the CRT coefficient of q_i, P the one
    special prime, rows basis then P."""
    primes = ctx.qs
    full = primes + (ctx.special,)
    s_full = ctx._secret_poly(full)
    Q = math.prod(primes)
    fk = s_full.mul(s_full)
    evk = []
    for qi in primes:
        Qi = Q // qi
        PTi = ctx.special * (Qi * pow(Qi % qi, -1, qi) % Q)
        a = rns.uniform_ntt(ctx.rng, full, ctx.n)
        e = ctx._noise_poly(full)
        evk.append((e.sub(a.mul(s_full)).add(
            fk.mul_scalar_per_prime({q: PTi % q for q in full})), a))
    return evk


@pytest.mark.parametrize("named", [False, True], ids=["defaults", "named"])
def test_default_shape_keeps_single_prime_chain_keys_and_tables(named):
    n, levels = 1024, 3
    kw = dict(dnum=levels + 1, special_primes=1) if named else {}
    ctx = CkksContext(n=n, levels=levels, seed=9, **kw)
    primes = rns.make_primes(n, levels + 2)
    assert ctx.specials == (primes[0],) and ctx.special == primes[0]
    assert ctx.qs == tuple(primes[1:])
    assert all(len(g) == 1 for g in ctx.digit_groups(ctx.qs))
    single = _single_prime_relin_keys(CkksContext(n=n, levels=levels, seed=9))
    assert _same(_keys(ctx.relin_keys(ctx.qs)), _keys(single))
    t, _ = ctx.plan().keyswitch_tables(ctx.qs)
    assert "up" not in t and "down" not in t
    want = rns.basis_pack(ctx.qs + ctx.specials, n)
    assert set(t) == set(want)
    assert all(np.array_equal(np.asarray(t[k]), np.asarray(want[k]))
               for k in t)


@pytest.mark.parametrize("shape", ["default", "grouped"])
def test_conversion_kernel_only_for_grouped_shapes(monkeypatch, shape):
    """A key switch of one-prime digits and one special prime calls no
    base conversion (the centred lift and the one-row mod-down); a
    grouped one calls it for the mod-up and for each half's mod-down,
    each op named for its role."""
    kw = {} if shape == "default" else dict(dnum=2, special_primes=3)
    ctx = CkksContext(n=256, levels=LEVELS, seed=3, **kw)
    t, fsp = ctx.plan().keyswitch_tables(ctx.qs)
    calls = []
    real = ops.base_conv_banks
    monkeypatch.setattr(ops, "base_conv_banks", lambda *a, **k: (
        calls.append((k["name"], a[0].shape)) or real(*a, **k)))
    k, d = len(ctx.qs), len(ctx.digit_groups(ctx.qs))
    kp = k + len(ctx.specials)
    d2 = jnp.zeros((k, 2, ctx.n), jnp.uint32)
    evk = jnp.zeros((d, kp, ctx.n), jnp.uint32)
    jax.make_jaxpr(lambda x: FB.batched_keyswitch(x, evk, evk, t, fsp=fsp))(d2)
    if shape == "default":
        assert calls == []
    else:
        down = (base_conv_kernel.MODDOWN, (1, 3, 2, ctx.n))
        assert calls == [(base_conv_kernel.MODUP, (d, 3, 2, ctx.n)), down,
                         down]


@pytest.mark.parametrize("kw", [{}, dict(dnum=2, special_primes=3)],
                         ids=["default", "grouped"])
def test_lower_basis_takes_top_level_key_rows(kw):
    ctx = CkksContext(n=256, levels=LEVELS, seed=4, **kw)
    top_b, top_a = _keys(ctx.galois_keys(5, ctx.qs))
    draws = ctx.rng.bit_generator.state
    for cut in (1, 2, 4):
        basis = ctx.qs[:-cut]
        low_b, low_a = _keys(ctx.galois_keys(5, basis))
        rows = list(range(len(basis))) + list(range(len(ctx.qs),
                                                    top_b.shape[1]))
        digits = len(ctx.digit_groups(basis))
        assert np.array_equal(low_b, top_b[:digits][:, rows])
        assert np.array_equal(low_a, top_a[:digits][:, rows])
    assert ctx.rng.bit_generator.state == draws      # no keygen below the top


@pytest.mark.parametrize("kw", [dict(dnum=0), dict(dnum=6),
                                dict(dnum=2, special_primes=2)],
                         ids=["dnum0", "dnum_over_limbs", "P_below_digit"])
def test_bad_keyswitch_shape_is_refused(kw):
    with pytest.raises(ValueError):
        CkksContext(n=256, levels=LEVELS, **kw)


def test_grouped_context_has_no_single_special_prime():
    ctx = CkksContext(n=256, levels=LEVELS, dnum=3, special_primes=2)
    assert len(ctx.specials) == 2
    with pytest.raises(ValueError):
        ctx.special


# ------------------------------------------------------- tracing

def test_keyswitch_spans_carry_digits_and_alpha():
    ctx = CkksContext(n=256, levels=LEVELS, seed=6, dnum=2, special_primes=3)
    plan = ctx.plan()
    rng = np.random.default_rng(2)
    cts = [ctx.encrypt(ctx.encode(rng.uniform(-1, 1, ctx.slots)))
           for _ in range(3)]
    obs.clear()
    obs.enable()
    try:
        plan.multiply_many(cts, cts)
        plan.rescale_many(cts)
        counters = obs.snapshot()["counters"]
    finally:
        obs.disable()
    progs = {e["args"]["program"]: e["args"] for e in obs.events()
             if e["name"] == "plan.program"}
    assert progs["multiply_many"]["digits"] == 2
    assert progs["multiply_many"]["alpha"] == 3
    assert "digits" not in progs["rescale_many"]
    assert counters["plan.digits"] == 3 * 2
    assert set(plan.stats) == {"dispatches", "key_switches", "decomposes"}


# ---------------------------------------------------------- meshes

def test_meshes_refuse_grouped_shapes():
    ctx = CkksContext(n=256, levels=LEVELS, dnum=3, special_primes=2)
    mesh = compat.make_mesh((1,), ("b",))
    with pytest.raises(NotImplementedError, match="grouped-digit"):
        EV.EvalPlan(ctx, mesh=mesh)
    with pytest.raises(NotImplementedError, match="grouped-digit"):
        EV.EvalPlan(ctx, mesh=compat.make_mesh((1,), ("k",)))
    t, fsp = ctx.plan().keyswitch_tables(ctx.qs)
    k, d = len(ctx.qs), len(ctx.digit_groups(ctx.qs))
    ct = jnp.zeros((1, k, ctx.n), jnp.uint32)
    evk = jnp.zeros((d, k + 2, ctx.n), jnp.uint32)
    with pytest.raises(NotImplementedError, match="grouped-digit"):
        EV.sharded_many_programs(mesh)["multiply"](ct, ct, ct, ct, evk, evk,
                                                   t, fsp)
