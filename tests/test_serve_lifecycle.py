"""The serve engine's per-request lifecycle record and the plan's
batch-key spans (``repro.obs``).

  * both drains, on a backlog and an open-loop trace, emit exactly one
    ``serve.request`` per resolved request, with due <= admitted <=
    dispatched <= ready; a request resolved at admission has all four
    equal, and every ``batch`` names a ``serve.dispatch`` span;
  * with tracing off no record is kept;
  * ``plan.batch_key`` reports a miss, then a hit for a repeated g
    pattern, and a miss again after the LRU evicted it.
"""
import dataclasses

import numpy as np
import pytest

from repro import obs
from repro.fhe import linalg
from repro.fhe.ckks import CkksContext
from repro.fhe.serve import CkksServeEngine, FheRequest

CTX = CkksContext(n=256, levels=2, scale_bits=26, seed=91)


@pytest.fixture(autouse=True)
def _obs_clean():
    obs.disable()
    obs.clear()
    obs.reset()
    yield
    obs.disable()
    obs.clear()
    obs.reset()


def _ct(rng):
    z = rng.uniform(-1, 1, CTX.slots) + 1j * rng.uniform(-1, 1, CTX.slots)
    return CTX.encrypt(CTX.encode(z))


def _requests():
    """Two galois groups' worth of rotations, a multiply, a rescale, an
    identity rotation and an exhausted rescale (both resolved at
    admission)."""
    rng = np.random.default_rng(92)
    plan = CTX.plan()
    ct = _ct(rng)
    low = plan.rescale(plan.rescale(ct))
    return [
        FheRequest(0, "rotate", _ct(rng), r=1),
        FheRequest(1, "multiply", _ct(rng), other=_ct(rng)),
        FheRequest(2, "rotate", _ct(rng), r=0),        # identity
        FheRequest(3, "rotate", _ct(rng), r=3),
        FheRequest(4, "rescale", low),                 # fails at admission
        FheRequest(5, "rescale", _ct(rng)),
        FheRequest(6, "conjugate", _ct(rng)),
    ]


AT_ADMISSION = {2, 4}


def _by_name(name):
    return [e for e in obs.events() if e["name"] == name]


@pytest.mark.parametrize("mode,open_loop", [
    ("sync", False), ("async", False), ("async", True)])
def test_one_serve_request_per_resolved_request(mode, open_loop):
    reqs = _requests()
    arrivals = [0.002 * i for i in range(len(reqs))] if open_loop else None
    engine = CkksServeEngine(CTX.plan(), batch_tile=2)
    obs.enable()
    if mode == "sync":
        out = engine.run(reqs)
    else:
        out = engine.run_async(reqs, arrivals)
    obs.disable()
    resolved = set(out) | set(engine.stats["failed"])
    assert resolved == {r.rid for r in reqs}
    evs = _by_name("serve.request")
    assert sorted(e["args"]["rid"] for e in evs) == sorted(resolved)
    batches = {e["args"]["batch"]: e["args"]["kind"]
               for e in _by_name("serve.dispatch")}
    assert len(batches) == engine.stats["dispatches"]
    ops = {r.rid: r.op for r in reqs}
    for e in evs:
        a = e["args"]
        assert a["kind"] == ops[a["rid"]]
        assert 0.0 <= a["admitted_us"] <= a["dispatched_us"] <= e["dur_us"]
        if a["rid"] in AT_ADMISSION:
            assert a["admitted_us"] == a["dispatched_us"] == e["dur_us"] == 0
            assert a["batch"] is None
        else:
            assert a["batch"] in batches
            assert batches[a["batch"]] == CkksServeEngine._kind(
                next(r for r in reqs if r.rid == a["rid"]))
    # requests of one batch share its dispatch and ready times
    for b in batches:
        rows = [e for e in evs if e["args"]["batch"] == b]
        ready = {round(e["ts_us"] + e["dur_us"], 3) for e in rows}
        disp = {round(e["ts_us"] + e["args"]["dispatched_us"], 3)
                for e in rows}
        assert len(ready) == 1 and len(disp) == 1
    dues = {e["args"]["rid"]: e["ts_us"] for e in evs
            if e["args"]["rid"] not in AT_ADMISSION}
    if open_loop:
        # due is t0 + arrival: the offsets between dues are the arrivals'
        for rid, due in dues.items():
            assert due - dues[0] == pytest.approx(
                (arrivals[rid] - arrivals[0]) * 1e6, abs=1.0)
    else:
        assert max(dues.values()) - min(dues.values()) < 1.0


def test_matvec_failing_inside_its_group_is_ready_at_dispatch():
    rng = np.random.default_rng(93)
    M = linalg.PtMatrix.encode(CTX, rng.uniform(-0.5, 0.5, (8, 4)))
    poisoned = dataclasses.replace(M, diags={**M.diags, (0, 0): "poison"})
    vcts = [CTX.encrypt(linalg.encode_vector(CTX, rng.uniform(-1, 1, 8), 4))
            for _ in range(2)]
    engine = CkksServeEngine(CTX.plan(), batch_tile=2)
    obs.enable()
    out = engine.run_async([FheRequest(0, "matvec", vcts[0], matrix=poisoned),
                            FheRequest(1, "matvec", vcts[1], matrix=M)])
    obs.disable()
    assert set(out) == {1} and set(engine.stats["failed"]) == {0}
    ev = {e["args"]["rid"]: e for e in _by_name("serve.request")}
    (disp,) = _by_name("serve.dispatch")
    assert disp["args"]["kind"] == "matvec"
    assert ev[0]["args"]["batch"] == ev[1]["args"]["batch"] == \
        disp["args"]["batch"]
    assert ev[0]["dur_us"] == ev[0]["args"]["dispatched_us"]
    assert ev[1]["dur_us"] >= ev[1]["args"]["dispatched_us"]


def test_tracing_off_keeps_no_record(monkeypatch):
    """Disabled, the drains never build the record: ``_life`` stays
    None while groups drain, and no lifecycle helper runs."""
    engine = CkksServeEngine(CTX.plan(), batch_tile=2)
    seen = []
    block = engine._block_outs

    def spy(outs):
        seen.append(engine._life)
        block(outs)

    def boom(*a, **k):
        raise AssertionError("lifecycle probe ran with tracing off")

    monkeypatch.setattr(engine, "_block_outs", spy)
    for helper in ("_admit", "_dispatched", "_ready", "_emit"):
        monkeypatch.setattr(engine, helper, boom)
    reqs = _requests()
    out = engine.run_async(reqs, [0.001 * i for i in range(len(reqs))])
    assert len(out) == len(reqs) - 1
    assert seen and all(life is None for life in seen)
    assert engine._life is None
    assert obs.events() == []
    # the no-op span singleton is still what a disabled span returns
    assert obs.span("serve.request") is obs.NOOP_SPAN


def test_batch_key_miss_hit_and_eviction(monkeypatch):
    rng = np.random.default_rng(94)
    plan = CTX.plan()              # shared: start from an empty cache
    basis = _ct(rng).primes
    g = [plan.rotation_group_element(r) for r in (1, 2, 3)]
    monkeypatch.setattr(plan, "_batch_keys", {})
    monkeypatch.setattr(plan, "_BATCH_KEY_CACHE_MAX", 1)
    obs.enable()
    first = plan._galois_batch_key((g[0], g[1]), basis)
    plan._galois_batch_key((g[0], g[1]), basis)     # hit
    plan._galois_batch_key((g[2], g[2]), basis)     # miss, evicts the first
    plan._galois_batch_key((g[0], g[1]), basis)     # miss again
    obs.disable()
    evs = _by_name("plan.batch_key")
    assert [e["args"]["hit"] for e in evs] == [False, True, False, False]
    assert all(e["args"]["b"] == 2 for e in evs)
    # resident bytes: the one cached pattern, from its shapes
    want = sum(x.nbytes for x in first)
    assert evs[0]["args"]["resident_bytes"] == want
    assert "resident_bytes" not in evs[1]["args"]
    assert evs[2]["args"]["resident_bytes"] == want
    assert len(plan._batch_keys) == 1
