"""The CKKS reference, harness and checks over grouped digits and several
special primes (``reference/ckks_ref.py``'s module doc): the reference
against a Python-integer oracle, decryption, the key-noise check, the
control, the configuration's refusals, and that naming today's shape
changes nothing."""
import numpy as np
import pytest

import grouped as G
from harness import ckks, spec
from reference.ckks_ref import CkksRef

N, LEVELS = 1 << 10, 5                     # 6 data primes
SHAPES = ((2, 3), (3, 2))                  # (dnum, special primes)


def _setup(dnum, alpha, seed=15, **kw):
    ref = CkksRef(N, LEVELS, "natural", dnum=dnum, special_primes=alpha, **kw)
    rng = np.random.default_rng([seed, dnum, alpha])
    s = G.secret(rng, N)
    full = ref.qs + ref.specials
    s_to = G.s_from(ref, s, full)
    key = G.make_key(ref, s, s_to, rng, gadget=G.gadgets(ref, dnum))
    return ref, rng, s, s_to, key


def _rows(rng, basis):
    return rng.integers(0, 2**62, (len(basis), N),
                        dtype=np.uint64) % G.col(basis)


@pytest.mark.parametrize("dnum,alpha", SHAPES)
@pytest.mark.parametrize("cut", (0, 1, 3))
def test_grouped_keyswitch_matches_the_oracle_and_decrypts(dnum, alpha, cut):
    """(a) Row for row equal to the Python-integer oracle, at the top
    level and at bases that cut a group or drop one; decrypting under s
    gives d*s' plus an error under ``grouped.error_bound``."""
    ref, rng, s, s_to, key = _setup(dnum, alpha)
    basis = ref.qs[:len(ref.qs) - cut]
    kb, ka = G.at_basis(ref, dnum, key, basis)
    assert kb.shape == (len(G.groups(ref, dnum, basis)),
                        len(basis) + alpha, N)
    d = _rows(rng, basis)
    c0, c1 = ref.keyswitch(d, basis, kb, ka)
    o0, o1 = G.oracle_keyswitch(ref, dnum, d, basis, kb, ka)
    assert np.array_equal(c0, o0) and np.array_equal(c1, o1)
    err = G.decrypt_error(ref, c0, c1, d, s, s_to, basis)
    assert err <= G.error_bound(ref, dnum, basis), err


def test_digits_follow_the_top_level_groups():
    ref = CkksRef(N, LEVELS, "natural", dnum=3, special_primes=2)
    assert ref.digits(ref.qs) == [(0, 1), (2, 3), (4, 5)]
    assert ref.digits(ref.qs[:5]) == [(0, 1), (2, 3), (4,)]
    assert ref.digits(ref.qs[:2]) == [(0, 1)]
    assert len(ref.specials) == 2 and ref.P == ref.specials[0] * \
        ref.specials[1]
    assert min(ref.specials) > max(ref.qs)
    ref = CkksRef(N, 6, "natural", dnum=3, special_primes=3)  # 7 primes: g=3
    assert ref.digits(ref.qs) == [(0, 1, 2), (3, 4, 5), (6,)]


def test_naming_todays_shape_changes_no_reference_reading():
    """(b), in the reference: dnum = levels+1 and one special prime read
    bit for bit as leaving them out."""
    for levels, order in ((2, "bitrev"), (5, "natural")):
        a = CkksRef(N, levels, order)
        b = CkksRef(N, levels, order, dnum=levels + 1, special_primes=1)
        assert (a.qs, a.specials) == (b.qs, b.specials)
        rng = np.random.default_rng(levels)
        basis, full = a.qs, a.qs + a.specials
        s = G.secret(rng, N)
        key = G.make_key(a, s, G.s_from(a, s, full), rng,
                         gadget=G.gadgets(a, levels + 1))
        x, y = (_rows(rng, basis), _rows(rng, basis)), \
            (_rows(rng, basis), _rows(rng, basis))
        g = a.rotation_element(5)
        for ra, rb in ((a.multiply(x, y, basis, key),
                        b.multiply(x, y, basis, key)),
                       (a.galois(x, basis, g, key), b.galois(x, basis, g, key)),
                       (a.rescale(x, basis), b.rescale(x, basis))):
            assert all(np.array_equal(u, v) for u, v in zip(ra, rb))
        assert a.key_noise(*key, s, basis) == b.key_noise(*key, s, basis) \
            <= 8


@pytest.fixture
def accepting_todays_shape(monkeypatch):
    """The program's CkksContext, taking ``dnum`` and ``special_primes``
    where they name today's shape and refusing any other."""
    from repro.fhe import ckks as program

    real = program.CkksContext

    class Named(real):
        def __init__(self, *args, dnum, special_primes, **kw):
            if (dnum, special_primes) != (kw["levels"] + 1, 1):
                raise TypeError("only today's key-switch shape")
            super().__init__(*args, **kw)

    monkeypatch.setattr(program, "CkksContext", Named)


@pytest.mark.parametrize("traffic", ("ckks_serve_steady",
                                     "ckks_keyswitch_b32"))
def test_naming_todays_shape_reads_bit_identical(run_small, request,
                                                 traffic):
    """(b), through a whole small run: the same checks, the program's and
    the control's, with the two keys named as without them."""
    cell = ("ckks_n14_l8", traffic)
    plain = run_small(cell, control=True)
    request.getfixturevalue("accepting_todays_shape")
    named = run_small(cell, control=True,
                      config={"dnum": 3, "special_primes": 1})
    assert plain["checks"] == named["checks"]
    assert plain["checks"]["answers_mismatched"]["value"] == 0
    assert plain["correct"] and named["correct"]
    assert plain["control_checks"] == named["control_checks"]
    assert not named["control_correct"]


def _single_prime_gadget(ref, dnum):
    """T of the first prime of each group only, not of the group."""
    Q = ref.qs
    out = []
    for grp in G.groups(ref, dnum, Q):
        rest = int(np.prod([q for q in Q if q != grp[0]], dtype=object))
        out.append(rest * pow(rest, -1, grp[0]))
    return out


@pytest.mark.parametrize("dnum,alpha", SHAPES)
@pytest.mark.parametrize("fault", (None, "single_prime_gadget", "other_s",
                                   "special_row_dropped", "P_short"))
def test_key_noise_of_grouped_keys(dnum, alpha, fault):
    """(c) An honest grouped key reads under the limit; single-prime
    gadgets, a key for another s', a special row dropped (the key's row,
    or a special prime left out of P) read far above it."""
    ref, rng, s, s_to, key = _setup(dnum, alpha)
    full = ref.qs + ref.specials
    if fault == "single_prime_gadget":
        key = G.make_key(ref, s, s_to, rng,
                         gadget=_single_prime_gadget(ref, dnum))
    elif fault == "other_s":
        key = G.make_key(ref, s, G.eval_rows(ref, s, full), rng,
                         gadget=G.gadgets(ref, dnum))
    elif fault == "special_row_dropped":
        key = tuple(k[:, :-1] for k in key)
    elif fault == "P_short":
        key = G.make_key(ref, s, s_to, rng, gadget=G.gadgets(ref, dnum),
                         P=ref.P // ref.specials[-1])
    noise = ref.key_noise(*key, s, ref.qs)
    if fault is None:
        assert noise <= 8 < ckks.KEY_NOISE_LIMIT
    else:
        assert noise > 2**16 * ckks.KEY_NOISE_LIMIT, noise


@pytest.mark.parametrize("dnum,alpha", SHAPES)
def test_floored_control_differs_on_grouped_keys(dnum, alpha):
    """(d) The control's floored mod-down gives other rows than the
    rounded one, through a key switch and a relinearising multiply."""
    ref, rng, s, s_to, key = _setup(dnum, alpha)
    ctl = CkksRef(N, LEVELS, "natural", dnum=dnum, special_primes=alpha,
                  round_mod_down=False)
    basis = ref.qs
    d = _rows(rng, basis)
    got, want = ctl.keyswitch(d, basis, *key), ref.keyswitch(d, basis, *key)
    assert not np.array_equal(got[0], want[0])
    assert not np.array_equal(got[1], want[1])
    x, y = (_rows(rng, basis), _rows(rng, basis)), \
        (_rows(rng, basis), _rows(rng, basis))
    assert not np.array_equal(ctl.multiply(x, y, basis, key)[0],
                              ref.multiply(x, y, basis, key)[0])


def _cfg(**kw):
    from conftest import small
    cfg = small("ckks_n14_l8", "ckks_serve_steady")[2]
    cfg.update(kw)
    return cfg


@pytest.mark.parametrize("keys,why", (
    ({"dnum": 0}, "dnum 0 outside"),
    ({"levels": 5, "dnum": 7}, "dnum 7 outside"),
    ({"dnum": 2.0}, "not a whole number"),
    ({"special_primes": 0}, "special_primes 0 under"),
    ({"levels": 5, "dnum": 2, "special_primes": 2}, "special_primes 2 under"),
    ({"n": 1 << 24, "levels": 7}, "primes = 1 (mod"),
))
def test_configuration_refusals(keys, why):
    """(e) Each refusal of the key-switch shape is a SpecError, raised
    before the program is built."""
    with pytest.raises(spec.SpecError, match=why.replace("(", r"\(")):
        ckks.keyswitch_shape(_cfg(**keys))


def test_configuration_shapes_accepted():
    assert ckks.keyswitch_shape(_cfg()) == {}
    assert ckks.keyswitch_shape(_cfg(levels=5, dnum=2, special_primes=3)) \
        == {"dnum": 2, "special_primes": 3}
    assert ckks.keyswitch_shape(_cfg(dnum=3)) == {"dnum": 3}


class _Built(Exception):
    pass


@pytest.mark.parametrize("keys", ({}, {"dnum": 3}, {"special_primes": 2},
                                  {"dnum": 2, "special_primes": 2}))
def test_cell_forwards_named_keys_only(monkeypatch, keys):
    """(g) CkksCell gives CkksContext dnum= and special_primes= where the
    configuration names them, and nothing of them where it does not."""
    from conftest import small
    from repro.fhe import ckks as program

    calls = []

    def record(**kw):
        calls.append(kw)
        raise _Built

    monkeypatch.setattr(program, "CkksContext", record)
    _, _, cfg, tr = small("ckks_n14_l8", "ckks_keyswitch_b32")
    cfg.update(keys)
    with pytest.raises(_Built):
        ckks.CkksCell(cfg, tr, 7)
    assert calls == [dict(n=cfg["n"], levels=cfg["levels"],
                          scale_bits=cfg["scale_bits"], seed=7, **keys)]


def test_refused_configuration_never_builds_the_program(monkeypatch):
    from conftest import small
    from repro.fhe import ckks as program

    monkeypatch.setattr(program, "CkksContext", lambda **kw: 1 / 0)
    _, _, cfg, tr = small("ckks_n14_l8", "ckks_keyswitch_b32")
    cfg.update(dnum=1, special_primes=1)
    with pytest.raises(spec.SpecError):
        ckks.CkksCell(cfg, tr, 7)


@pytest.mark.parametrize("keys", ({"dnum": 3}, {"special_primes": 1}))
def test_keys_the_program_does_not_take_are_refused(monkeypatch, keys):
    """A named key that the program's CkksContext has no keyword for is a
    SpecError before the program is built, not a TypeError inside it."""
    from conftest import small
    from repro.fhe import ckks as program

    def takes_no_shape(n, levels, scale_bits=28, sigma=3.2, seed=0):
        raise _Built

    monkeypatch.setattr(program, "CkksContext", takes_no_shape)
    _, _, cfg, tr = small("ckks_n14_l8", "ckks_keyswitch_b32")
    cfg.update(keys)
    with pytest.raises(spec.SpecError, match="does not take"):
        ckks.CkksCell(cfg, tr, 7)
    assert ckks.untaken_keys(takes_no_shape, {}) == []
