#!/usr/bin/env python3
"""End-to-end smoke run of the system on a TPU, through its entry points.

    python chip_smoke.py              # one chip: ntt128, ckks_serve, mlkem
    python chip_smoke.py --chips 4    # four chips: the sharded EvalPlan only

Phases (each prints one line: name, ok, wall seconds including compile,
backend compile seconds, device kind, and what it checked):

  ntt128      the fabricated NTT-128 unit at the ``configs/sce_ntt.py``
              shape (n=128, 8 RNS limbs, batch 4096) through
              ``ops.ntt_banks``/``intt_banks``: sampled rows against the
              O(n^2) golden model, every row against the XLA reference
              path, and the negacyclic round trip.
  ckks_serve  ``CkksContext(n=2^14, levels=7)`` (8 limbs + the special
              prime: the paper's Fig 22 key switch), a prepared plan and
              a ``CkksServeEngine`` draining a mixed trace of 32
              requests (multiply, mixed-amount rotate, conjugate,
              rescale, one BSGS matvec) with ``run_async``.  Requires no
              failed request, no fresh trace after ``prepare``, answers
              bit-exact to single-op ``plan.*`` calls, one key switch
              bit-exact to the host oracle ``fhe/keyswitch.py``, and
              decrypted slots within the CKKS error bound of numpy.
  mlkem       ML-KEM-768 keygen/encaps/decaps at b=64 (matching shared
              secrets) and the committed KATs.
  sharded     (``--chips 4`` only) ``multiply_many`` and ``rotate_many``
              over B=32 at the same 2^14 / 8-limb context on a 4-device
              "b" mesh, bit-exact to the single-device program, output
              shards on 4 distinct devices.

The last line of standard output is the JSON contract line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when the default backend is a TPU and every phase passed;
otherwise the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

KAT_PATH = os.path.join(HERE, "tests", "vectors", "mlkem768_kat.json")
CKKS_N = 1 << 14
CKKS_LEVELS = 7                 # L+1 = 8 limbs, plus the special prime
SERVE_TILE = 8


class _CompileClock:
    """Sums JAX's backend-compile durations while a phase runs."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.total += secs


def _ct_equal(a, b) -> bool:
    import numpy as np
    return (np.array_equal(np.asarray(a.c0.data), np.asarray(b.c0.data))
            and np.array_equal(np.asarray(a.c1.data), np.asarray(b.c1.data))
            and a.scale == b.scale and a.primes == b.primes)


def _require(cond, what):
    if not cond:
        raise AssertionError(what)


# ------------------------------------------------------------- phases

def phase_ntt128(seed):
    import jax
    import numpy as np
    from repro.configs.sce_ntt import CONFIG
    from repro.core.ntt import brute_ntt_bitrev_np
    from repro.core.params import gen_ntt_primes, make_ntt_params
    from repro.fhe import batched as FB
    from repro.kernels import ops

    n, k, b = CONFIG.ring_n, CONFIG.rns_limbs, CONFIG.batch
    primes = gen_ntt_primes(k, n, bits=30)
    t = FB.build_table_pack(primes, n)
    rng = np.random.default_rng(seed)
    x = np.stack([rng.integers(0, q, (b, n), dtype=np.uint32) for q in primes])
    got = np.asarray(ops.ntt_banks(x, t, negacyclic=False))
    want = np.asarray(ops.ntt_banks(x, t, negacyclic=False, use_pallas=False))
    _require(np.array_equal(got, want), "ntt_banks != XLA reference path")
    rows = rng.choice(b, 16, replace=False)
    for i, q in enumerate(primes):
        gold = brute_ntt_bitrev_np(x[i, rows], make_ntt_params(n, q=q).omega, q)
        _require(np.array_equal(got[i, rows], gold),
                 f"prime row {i}: ntt_banks != golden model")
    y = ops.ntt_banks(x, t, negacyclic=True)
    back = np.asarray(jax.block_until_ready(ops.intt_banks(y, t,
                                                           negacyclic=True)))
    _require(np.array_equal(back, x), "negacyclic round trip")
    return f"n={n} limbs={k} batch={b} golden_rows={len(rows)}x{k} roundtrip=OK"


def _slots(rng, slots):
    return rng.uniform(-1, 1, slots) + 1j * rng.uniform(-1, 1, slots)


def _fresh_bound(ctx) -> float:
    """Slot-error bound of a fresh ciphertext over its scale: B_clean of
    Cheon-Kim-Kim-Song (2017, Lemma 1), 8*sqrt(2)*s*N + 6*s*sqrt(N) +
    16*s*sqrt(h*N), with the secret's Hamming weight h taken as N."""
    import math
    n, s = ctx.n, ctx.sigma
    return (8 * math.sqrt(2) * s * n + 6 * s * math.sqrt(n)
            + 16 * s * n) / ctx.scale


def _slot_bounds(ctx, w_max: float, d_in: int) -> dict:
    """Per-op slot-error bounds in units of the fresh bound B: a Galois
    op adds one key switch (<= B); a product of two fresh |z| <= sqrt(2)
    operands carries 2*sqrt(2)*B plus its key switch, and a rescale one
    more rounding term; a matvec sums d_in rotated terms under
    |W| <= w_max."""
    b = _fresh_bound(ctx)
    return {"rotate": 2 * b, "conjugate": 2 * b, "multiply": 4 * b,
            "rescale": 5 * b, "matvec": (2 * d_in * w_max + 1) * b}


def phase_ckks_serve(seed):
    import jax
    import numpy as np
    from repro.fhe import linalg
    from repro.fhe.ckks import CkksContext
    from repro.fhe.evalplan import Ciphertext
    from repro.fhe.keyswitch import keyswitch as host_keyswitch
    from repro.fhe.serve import CkksServeEngine, FheRequest

    rng = np.random.default_rng(seed)
    ctx = CkksContext(n=CKKS_N, levels=CKKS_LEVELS, seed=seed)
    plan = ctx.plan()
    S = ctx.slots
    d_in = d_out = 16
    W = rng.uniform(-0.5, 0.5, (d_in, d_out))
    M = linalg.PtMatrix.encode(ctx, W)
    rots = (1, 2, 3)
    plan.prepare(rotations=rots, conjugate=True, batch_sizes=(SERVE_TILE,),
                 matvecs=(M,))
    engine = CkksServeEngine(plan, batch_tile=SERVE_TILE,
                             max_batch=SERVE_TILE)

    enc = lambda z: ctx.encrypt(ctx.encode(z))
    reqs, oracle = [], {}
    kinds = (["multiply"] * 8 + ["rotate"] * 12 + ["conjugate"] * 6
             + ["rescale"] * 5 + ["matvec"])
    for rid, kind in enumerate(kinds):
        if kind == "matvec":
            x = rng.uniform(-1, 1, d_in)
            reqs.append(FheRequest(rid, "matvec", ctx.encrypt(
                linalg.encode_vector(ctx, x, d_out)), matrix=M))
            oracle[rid] = (x @ W, d_out)
            continue
        z = _slots(rng, S)
        ct = enc(z)
        if kind == "multiply":
            z2 = _slots(rng, S)
            reqs.append(FheRequest(rid, kind, ct, other=enc(z2)))
            oracle[rid] = (z * z2, S)
        elif kind == "rotate":
            r = rots[rid % len(rots)]
            reqs.append(FheRequest(rid, kind, ct, r=r))
            oracle[rid] = (np.roll(z, -r), S)
        elif kind == "conjugate":
            reqs.append(FheRequest(rid, kind, ct))
            oracle[rid] = (np.conj(z), S)
        else:                   # rescale a product (scale^2 -> ~scale)
            z2 = _slots(rng, S)
            reqs.append(FheRequest(rid, kind, plan.multiply(ct, enc(z2))))
            oracle[rid] = (z * z2, S)

    out = engine.run_async(reqs)
    st = engine.stats
    _require(not st["failed"], f"failed requests: {st['failed']}")
    _require(st["fresh_traces"] == 0,
             f"fresh_traces={st['fresh_traces']} after prepare")
    _require(sorted(out) == [r.rid for r in reqs], "missing answers")

    single = {
        "multiply": lambda q: plan.multiply(q.ct, q.other),
        "rotate": lambda q: plan.rotate(q.ct, q.r),
        "conjugate": lambda q: plan.conjugate(q.ct),
        "rescale": lambda q: plan.rescale(q.ct),
        "matvec": lambda q: linalg.matvec(plan, q.matrix, q.ct),
    }
    bounds = _slot_bounds(ctx, float(np.max(np.abs(W))), d_in)
    err = dict.fromkeys(bounds, 0.0)
    for q in reqs:
        _require(_ct_equal(out[q.rid], single[q.op](q)),
                 f"request {q.rid} ({q.op}): engine != single-op plan call")
        want, m = oracle[q.rid]
        got = ctx.decrypt_decode(out[q.rid])[:m]
        err[q.op] = max(err[q.op], float(np.max(np.abs(got - want[:m]))))
    errs = " ".join(f"{op}={e:.3g}/{bounds[op]:.3g}" for op, e in err.items())
    _require(all(err[op] < bounds[op] for op in err),
             f"slot error over the CKKS bound (max/bound): {errs}")

    # one key switch against the host-orchestrated oracle: the fused
    # multiply program == tensor + fhe/keyswitch.py digit loop
    a, b = reqs[0].ct, reqs[0].other
    d0 = a.c0.mul(b.c0)
    d1 = a.c0.mul(b.c1).add(a.c1.mul(b.c0))
    ks0, ks1 = host_keyswitch(a.c1.mul(b.c1), ctx.relin_keys(a.primes),
                              ctx.special)
    host = Ciphertext(d0.add(ks0), d1.add(ks1), a.scale * b.scale)
    _require(_ct_equal(out[reqs[0].rid], host),
             "multiply key switch != host oracle fhe/keyswitch.py")
    jax.block_until_ready(host.c0.data)
    return (f"n=2^{ctx.n.bit_length() - 1} limbs={len(ctx.qs)} requests={len(reqs)} "
            f"dispatches={st['dispatches']} key_switches={st['key_switches']} "
            f"fresh_traces={st['fresh_traces']} failed=0 "
            f"bitexact_single_op=OK host_keyswitch=OK "
            f"slot_err_max/bound: {errs}")


def phase_mlkem(seed):
    import numpy as np
    from repro.pq import mlkem

    rng = np.random.default_rng(seed)
    b = 64
    d, z, m = (rng.integers(0, 256, (b, 32), dtype=np.uint8) for _ in range(3))
    ek, dk = mlkem.keygen_batch(d, z)
    key, ct = mlkem.encaps_batch(ek, m)
    back = mlkem.decaps_batch(dk, ct)
    _require(np.array_equal(key, back), "encaps/decaps shared secrets differ")
    with open(KAT_PATH) as f:
        vs = json.load(f)["vectors"]
    h = lambda name: np.stack([np.frombuffer(bytes.fromhex(v[name]), np.uint8)
                               for v in vs])
    ek2, dk2 = mlkem.keygen_batch(h("d"), h("z"))
    key2, ct2 = mlkem.encaps_batch(ek2, h("m"))
    ok = (np.array_equal(ek2, h("ek")) and np.array_equal(dk2, h("dk"))
          and np.array_equal(ct2, h("ct")) and np.array_equal(key2, h("K"))
          and np.array_equal(mlkem.decaps_batch(dk2, ct2), h("K")))
    _require(ok, "ML-KEM-768 KATs do not reproduce")
    return f"b={b} shared_secrets=OK kat={len(vs)}/{len(vs)}"


def phase_sharded(seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import compat
    from repro.fhe.ckks import CkksContext
    from repro.fhe.evalplan import EvalPlan, sharded_many_programs

    devs = jax.devices()
    _require(len(devs) >= 4, f"need 4 devices, have {len(devs)}")
    rng = np.random.default_rng(seed)
    ctx = CkksContext(n=CKKS_N, levels=CKKS_LEVELS, seed=seed)
    B = 32
    enc = lambda: ctx.encrypt(ctx.encode(_slots(rng, ctx.slots)))
    As = [enc() for _ in range(B)]
    Bs = [enc() for _ in range(B)]
    rs = [int(r) for r in rng.integers(1, 4, B)]
    mesh = compat.make_mesh((4,), ("b",), devices=devs[:4])
    single = EvalPlan(ctx)
    sharded = EvalPlan(ctx, mesh=mesh)
    outs = {}
    for name, plan in (("single", single), ("sharded", sharded)):
        outs[name] = (plan.multiply_many(As, Bs), plan.rotate_many(As, rs))
        jax.block_until_ready([c.c0.data for grp in outs[name] for c in grp])
    for grp_s, grp_m in zip(outs["single"], outs["sharded"]):
        _require(all(_ct_equal(a, b) for a, b in zip(grp_s, grp_m)),
                 "sharded != single-device program")
    # the sharded program's output shards sit on four distinct devices,
    # B/4 ciphertext rows each
    basis = As[0].primes
    t, fsp = sharded.keyswitch_tables(basis)
    eb, ea = sharded.relin_key(basis)
    stack = lambda cts, half: jnp.stack([getattr(c, half).data for c in cts])
    c0, _ = sharded_many_programs(mesh)["multiply"](
        stack(As, "c0"), stack(As, "c1"), stack(Bs, "c0"), stack(Bs, "c1"),
        eb, ea, t, fsp)
    shards = c0.addressable_shards
    placed = {sh.device.id for sh in shards}
    _require(len(placed) == 4 and all(sh.data.shape[0] == B // 4
                                      for sh in shards),
             f"output shards on devices {sorted(placed)}, shapes "
             f"{[sh.data.shape for sh in shards]}")
    return (f"B={B} n=2^{ctx.n.bit_length() - 1} limbs={len(ctx.qs)} mesh=4x'b' "
            f"multiply_many+rotate_many bitexact_single=OK "
            f"shard_devices={sorted(placed)}")


PHASES_1 = (("ntt128", phase_ntt128), ("ckks_serve", phase_ckks_serve),
            ("mlkem", phase_mlkem))
PHASES_4 = (("sharded", phase_sharded),)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro import compile_cache
    cache_dir = compile_cache.enable()
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: default backend is {backend!r}, not a TPU",
              file=sys.stderr)
        return 2
    devs = jax.devices()
    kind = devs[0].device_kind
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"have {len(devs)}", file=sys.stderr)
        return 2
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile_cache={cache_dir} entries_at_start={cached}", flush=True)
    clock = _CompileClock()
    ok_all = True
    for name, fn in (PHASES_4 if args.chips == 4 else PHASES_1):
        t0, c0 = time.perf_counter(), clock.total
        try:
            detail, ok = fn(args.seed), True
        except Exception as e:          # noqa: BLE001 — report, then fail
            traceback.print_exc()
            detail, ok = f"error={type(e).__name__}: {e}", False
        ok_all &= ok
        print(f"phase={name} ok={ok} wall_s={time.perf_counter() - t0:.3f} "
              f"compile_s={clock.total - c0:.3f} device_kind={kind} {detail}",
              flush=True)
    if not ok_all:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
