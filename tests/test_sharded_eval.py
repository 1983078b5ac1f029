"""Sharded EvalPlan (PR 8): mesh-routed programs == single-device ones.

Three rings of coverage, matching what the container can actually run:

* tier-1 proper (this file, unmarked): a mesh of ONE device must route
  through the ``shard_map`` twins and produce bit-identical results to
  the plain plan — the no-op equivalence test, plus mesh validation,
  trace accounting and the serve engine's device-aware sizing.
* ``@skipif(device_count < 4)``: in-process 4-device checks that run
  under the CI job forcing ``--xla_force_host_platform_device_count=4``
  (and skip-with-reason on the 1-device local container).
* ``@slow`` + ``tests/subproc.py``: full 2^10-ring bit-exactness for
  multiply/rescale/rotate/matvec in a child process with 4 simulated
  host devices (skip-with-reason when the sandbox cannot spawn them).

Bit-exactness is the load-bearing claim: every sharded program is
per-shard compute over independent batch rows (no collectives), and the
k-axis GSPMD path is integer modular arithmetic (no association-order
effects), so equality is exact — never approximate.
"""
import jax
import numpy as np
import pytest

from subproc import run_multidevice
from repro import compat
from repro.fhe import linalg, serve
from repro.fhe.ckks import CkksContext
from repro.fhe.evalplan import EvalPlan
from repro.fhe import evalplan as EV

RNG = np.random.default_rng(0xA11CE)


@pytest.fixture(scope="module")
def ctx():
    return CkksContext(n=64, levels=2, seed=7)


@pytest.fixture(scope="module")
def plans(ctx):
    """(plain, mesh-of-1) plan pair over one context."""
    mesh = compat.make_mesh((1,), ("b",))
    return ctx.plan(), EvalPlan(ctx, mesh=mesh)


def _enc(ctx):
    z = RNG.uniform(-1, 1, ctx.slots) + 1j * RNG.uniform(-1, 1, ctx.slots)
    return ctx.encrypt(ctx.encode(z))


def _same_ct(a, b):
    assert a.primes == b.primes
    assert np.array_equal(np.asarray(a.c0.data), np.asarray(b.c0.data))
    assert np.array_equal(np.asarray(a.c1.data), np.asarray(b.c1.data))


def test_mesh_axis_names_validated(ctx):
    mesh = compat.make_mesh((1,), ("batch",))
    with pytest.raises(ValueError, match="mesh axis"):
        EvalPlan(ctx, mesh=mesh)


def test_mesh_of_one_is_sharded_and_counts_as_one_device(plans):
    _, sharded = plans
    assert sharded._sharded is not None     # size-1 "b" still shard-routes
    assert sharded.mesh_devices == 1
    assert len(EV._SHARDED_PROGRAMS) >= 5


def test_mesh_of_one_pallas_kernels_inside_shard_map(ctx, plans):
    """The kernel path inside the shard_map twins (what a TPU mesh runs):
    pallas_calls that mix replicated tables with batch-sharded blocks
    must trace there.  Interpret-mode kernels under a mesh of one == the
    plain plan, bit for bit."""
    plain, _ = plans
    sharded = EvalPlan(ctx, use_pallas=True,
                       mesh=compat.make_mesh((1,), ("b",)))
    cts = [_enc(ctx) for _ in range(2)]
    bts = [_enc(ctx) for _ in range(2)]
    for a, b in zip(plain.multiply_many(cts, bts),
                    sharded.multiply_many(cts, bts)):
        _same_ct(a, b)
    for a, b in zip(plain.rotate_many(cts, [1, 2]),
                    sharded.rotate_many(cts, [1, 2])):
        _same_ct(a, b)


def test_mesh_of_one_batched_ops_bit_exact(ctx, plans):
    plain, sharded = plans
    cts = [_enc(ctx) for _ in range(5)]
    bts = [_enc(ctx) for _ in range(5)]
    for a, b in zip(plain.multiply_many(cts, bts),
                    sharded.multiply_many(cts, bts)):
        _same_ct(a, b)
    for a, b in zip(plain.rescale_many(cts), sharded.rescale_many(cts)):
        _same_ct(a, b)
    # mixed rotation amounts (incl. identity) — the galois_mixed program
    rs = [1, 2, 0, -1, 2]
    for a, b in zip(plain.rotate_many(cts, rs), sharded.rotate_many(cts, rs)):
        _same_ct(a, b)
    # uniform batch — the galois_shared program
    for a, b in zip(plain.conjugate_many(cts), sharded.conjugate_many(cts)):
        _same_ct(a, b)


def test_mesh_of_one_hoisted_and_matvec_bit_exact(ctx, plans):
    plain, sharded = plans
    ct = _enc(ctx)
    for a, b in zip(plain.rotate_hoisted(ct, [1, 2, 3]),
                    sharded.rotate_hoisted(ct, [1, 2, 3])):
        _same_ct(a, b)
    W = RNG.uniform(-1, 1, (8, 8))
    M = linalg.PtMatrix.encode(ctx, W)
    _same_ct(linalg.matvec(plain, M, ct), linalg.matvec(sharded, M, ct))


def test_mesh_of_one_single_ct_ops_bit_exact(ctx, plans):
    plain, sharded = plans
    a, b = _enc(ctx), _enc(ctx)
    _same_ct(plain.multiply(a, b), sharded.multiply(a, b))
    _same_ct(plain.rescale(a), sharded.rescale(a))
    _same_ct(plain.rotate(a, 2), sharded.rotate(a, 2))


def test_trace_count_covers_sharded_programs(ctx, plans):
    """A fresh sharded jit signature must show up in ``trace_count`` —
    the serve engine's ``fresh_traces`` discipline depends on it."""
    _, sharded = plans
    sig = lambda: sum(getattr(p, "_cache_size", lambda: 0)()
                      for p in EV._SHARDED_PROGRAMS)
    before_sharded, before_total = sig(), EvalPlan.trace_count()
    cts = [_enc(ctx) for _ in range(7)]     # B=7: unique in this process
    sharded.rescale_many(cts)
    assert sig() > before_sharded
    assert EvalPlan.trace_count() - before_total >= sig() - before_sharded


def test_serve_engine_mesh_of_one_bit_exact(ctx, plans):
    """Both drains over a mesh-of-1 plan answer bit-identically to the
    plain-plan engine, and the per-device accounting is consistent."""
    plain, sharded = plans
    reqs, _ = serve.synthetic_trace(ctx, 12, seed=5)
    want = serve.CkksServeEngine(plain, batch_tile=4, max_batch=8).run(reqs)
    eng = serve.CkksServeEngine(sharded, batch_tile=4, max_batch=8)
    assert eng.devices == 1 and eng.group_tile == 4
    got = eng.run(reqs)
    assert set(got) == set(want)
    for rid in want:
        _same_ct(got[rid], want[rid])
    assert eng.stats["devices"] == 1
    assert sum(eng.stats["per_device_rows"]) == \
        eng.stats["batched_ops"] + eng.stats["padded"]
    got_async = eng.run_async(reqs)
    for rid in want:
        _same_ct(got_async[rid], want[rid])


# --------------------------------------------------------------------
# In-process 4-device ring: exercised by the CI job that forces
# --xla_force_host_platform_device_count=4 before pytest starts.

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason=f"needs 4 XLA devices, backend has {jax.device_count()} "
           "(CI forces 4 host devices via XLA_FLAGS)")


@needs4
def test_four_device_batched_ops_bit_exact(ctx):
    plain = ctx.plan()
    sharded = EvalPlan(ctx, mesh=compat.make_mesh((4,), ("b",)))
    cts = [_enc(ctx) for _ in range(6)]     # 6 -> pads to 8 over 4 devices
    bts = [_enc(ctx) for _ in range(6)]
    for a, b in zip(plain.multiply_many(cts, bts),
                    sharded.multiply_many(cts, bts)):
        _same_ct(a, b)
    for a, b in zip(plain.rescale_many(cts), sharded.rescale_many(cts)):
        _same_ct(a, b)
    rs = [1, 2, 3, 1, 0, 2]
    for a, b in zip(plain.rotate_many(cts, rs), sharded.rotate_many(cts, rs)):
        _same_ct(a, b)
    for a, b in zip(plain.rotate_hoisted(cts[0], [1, 2, 3]),
                    sharded.rotate_hoisted(cts[0], [1, 2, 3])):
        _same_ct(a, b)


@needs4
def test_four_device_serve_engine_saturates(ctx):
    plain = ctx.plan()
    sharded = EvalPlan(ctx, mesh=compat.make_mesh((4,), ("b",)))
    reqs, _ = serve.synthetic_trace(ctx, 16, seed=9)
    want = serve.CkksServeEngine(plain, batch_tile=2, max_batch=8).run(reqs)
    eng = serve.CkksServeEngine(sharded, batch_tile=2, max_batch=8)
    assert eng.devices == 4 and eng.group_tile == 8
    got = eng.run_async(reqs)
    for rid in want:
        _same_ct(got[rid], want[rid])
    assert eng.stats["devices"] == 4
    rows = eng.stats["per_device_rows"]
    assert len(rows) == 4 and len(set(rows)) == 1   # equally loaded
    assert sum(rows) == eng.stats["batched_ops"] + eng.stats["padded"]


# --------------------------------------------------------------------
# Slow ring: 2^10 ring in a 4-simulated-device child process.

pytest_slow = pytest.mark.slow

_CHILD_COMMON = """
    import numpy as np
    from repro import compat
    from repro.fhe import linalg
    from repro.fhe.ckks import CkksContext
    from repro.fhe.evalplan import EvalPlan

    # levels=3 -> a 4-prime basis, so the k-mesh child really shards
    # (k-sharding degrades to identity when k does not divide the axis)
    ctx = CkksContext(n=1024, levels=3, seed=11)
    plain = ctx.plan()
    rng = np.random.default_rng(3)
    def enc():
        z = rng.uniform(-1, 1, ctx.slots) + 1j*rng.uniform(-1, 1, ctx.slots)
        return ctx.encrypt(ctx.encode(z))
    def same(a, b):
        assert np.array_equal(np.asarray(a.c0.data), np.asarray(b.c0.data))
        assert np.array_equal(np.asarray(a.c1.data), np.asarray(b.c1.data))
"""


@pytest_slow
def test_sharded_b_mesh_2pow10_bit_exact():
    run_multidevice(_CHILD_COMMON + """
    plan = EvalPlan(ctx, mesh=compat.make_mesh((4,), ("b",)))
    cts = [enc() for _ in range(6)]
    bts = [enc() for _ in range(6)]
    for a, b in zip(plain.multiply_many(cts, bts),
                    plan.multiply_many(cts, bts)):
        same(a, b)
    for a, b in zip(plain.rescale_many(cts), plan.rescale_many(cts)):
        same(a, b)
    rs = [1, 5, 0, -2, 1, 3]
    for a, b in zip(plain.rotate_many(cts, rs), plan.rotate_many(cts, rs)):
        same(a, b)
    W = rng.uniform(-1, 1, (8, 8))
    M = linalg.PtMatrix.encode(ctx, W)
    same(linalg.matvec(plain, M, cts[0]), linalg.matvec(plan, M, cts[0]))
    print("SHARDED_B_OK")
    """, token="SHARDED_B_OK", devices=4, timeout=540)


@pytest_slow
def test_sharded_k_mesh_2pow10_bit_exact():
    run_multidevice(_CHILD_COMMON + """
    plan = EvalPlan(ctx, mesh=compat.make_mesh((2,), ("k",)))
    a, b = enc(), enc()
    same(plain.multiply(a, b), plan.multiply(a, b))
    same(plain.rescale(a), plan.rescale(a))
    same(plain.rotate(a, 3), plan.rotate(a, 3))
    cts = [enc() for _ in range(4)]
    bts = [enc() for _ in range(4)]
    for x, y in zip(plain.multiply_many(cts, bts),
                    plan.multiply_many(cts, bts)):
        same(x, y)
    print("SHARDED_K_OK")
    """, token="SHARDED_K_OK", devices=4, timeout=540)
