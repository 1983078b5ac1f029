"""``correct`` holds for the program, and comes out false for the control
and for each fault a cell can have, planted under a run at a CPU size.

Faults, each planted where the answer is produced: the step returns its
input state unchanged; the first half of each batch is left out (its
rows come back as zeros); every answer is altered by one coefficient or
one bit.  The cells run on one chip, so there is no exchange between
chips to leave out."""
import numpy as np
import pytest

CELLS = (("ckks_n14_l8", "ckks_serve_steady"),
         ("ckks_n14_l8", "ckks_keyswitch_b32"),
         ("mlkem768", "tls_capacity"))


def _zero_like(ct):
    from repro.fhe.evalplan import Ciphertext
    from repro.fhe.rns import RnsPoly
    z = RnsPoly(ct.c0.data * 0, ct.c0.primes, True)
    return Ciphertext(z, z, ct.scale)


def _bump(ct):
    from repro.fhe.evalplan import Ciphertext
    from repro.fhe.rns import RnsPoly
    c0 = RnsPoly(ct.c0.data.at[0, 0].set((ct.c0.data[0, 0] + 1)
                                         % ct.c0.primes[0]),
                 ct.c0.primes, True)
    return Ciphertext(c0, ct.c1, ct.scale)


def _ckks_fault(kind):
    def wrap(orig):
        def many(self, cts, *rest):
            outs = orig(self, cts, *rest)
            if kind == "unchanged":
                return list(cts)
            if kind == "half":
                h = (len(outs) + 1) // 2
                return [_zero_like(c) for c in outs[:h]] + list(outs[h:])
            return [_bump(c) for c in outs]
        return many
    return wrap


def _mlkem_fault(kind, orig):
    def encaps(ek, m):
        key, ct = orig(ek, m)
        key, ct = np.array(key), np.array(ct)
        if kind == "unchanged":
            return np.array(m, dtype=np.uint8), np.array(ek)[:, :ct.shape[1]]
        if kind == "half":
            h = (len(key) + 1) // 2
            key[:h] = 0
            ct[:h] = 0
            return key, ct
        key[:, 0] ^= 1
        return key, ct
    return encaps


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(run_small, cell):
    res = run_small(cell)
    assert res["correct"], res["checks"]
    assert res["checks"]["answers_mismatched"]["value"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(run_small, cell):
    res = run_small(cell, control=True)
    assert res["correct"], res["checks"]
    assert not res["control_correct"]
    assert res["control_checks"]["answers_mismatched"]["value"] > 0


@pytest.mark.parametrize("fault", ("unchanged", "half", "altered"))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(run_small, monkeypatch, cell, fault):
    from repro.fhe.evalplan import EvalPlan
    from repro.pq import mlkem

    if cell[0] == "mlkem768":
        monkeypatch.setattr(mlkem, "encaps_batch",
                            _mlkem_fault(fault, mlkem.encaps_batch))
    else:
        for name in ("multiply_many", "galois_ks_many", "rescale_many"):
            monkeypatch.setattr(EvalPlan, name,
                                _ckks_fault(fault)(getattr(EvalPlan, name)))
    res = run_small(cell)
    assert not res["correct"], res["checks"]


def test_half_batch_fault_is_seen_at_batch_32(run_small, monkeypatch):
    """The closed loop's check draws its rows stratified over the batch,
    so leaving out half of a 32-row batch is seen on every seed."""
    from repro.fhe.evalplan import EvalPlan

    monkeypatch.setattr(EvalPlan, "multiply_many",
                        _ckks_fault("half")(EvalPlan.multiply_many))
    for seed in (2**31 + 7, 2**31 + 8):
        res = run_small(("ckks_n14_l8", "ckks_keyswitch_b32"), seed=seed,
                        traffic={"batch": 32})
        assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ("relin_for_s", "galois_other_element",
                                   "gadget"))
def test_key_fault_is_not_correct(run_small, monkeypatch, fault):
    """A key made wrong at set-up gives the same answers on both sides;
    the key check against the secret key fails it."""
    from repro.fhe.ckks import CkksContext

    relin, galois = CkksContext.relin_keys, CkksContext.galois_keys
    if fault == "relin_for_s":
        monkeypatch.setattr(CkksContext, "relin_keys", lambda self, primes:
                            self._make_ksk(self._secret_poly(
                                primes + (self.special,)), primes))
    elif fault == "galois_other_element":
        monkeypatch.setattr(CkksContext, "galois_keys", lambda self, g, primes:
                            galois(self, g * 5 % (2 * self.n), primes))
    else:
        def shifted(self, primes):
            # digit keys shifted by one digit: the wrong CRT gadget T_i
            keys = relin(self, primes)
            return keys[1:] + keys[:1]
        monkeypatch.setattr(CkksContext, "relin_keys", shifted)
    res = run_small(("ckks_n14_l8", "ckks_serve_steady"))
    assert not res["correct"], res["checks"]
    assert res["checks"]["key_noise_max"]["value"] > \
        res["checks"]["key_noise_max"]["limit"]
