"""CKKS deployments: set-up from a configuration file and the seed, the
timed paths, and the bit-exact check against ``reference/ckks_ref.py``.

Configuration keys: ``n``, ``levels`` (L; the chain holds L+1 primes plus
the special primes), ``scale_bits``, ``ntt_order`` (slot order of the
evaluation form), ``rotations`` and ``conjugate`` (the tenant's Galois
keys), ``matrix`` ([d_in, d_out] of the tenant's BSGS plaintext matrix),
``batch_tile`` and ``max_batch`` (the serve engine's).

The hybrid key switch's shape, both keys optional:

* ``dnum``: the number of digits, 1..L+1; absent, L+1 (one prime each);
* ``special_primes``: alpha, the number of special primes whose product
  P divides the key switch; absent, 1.

With g = ceil((L+1)/dnum) primes per digit, a configuration is refused
(``spec.SpecError``) where alpha < max(1, g), which would leave P below a
digit's modulus, or where fewer than L+1+alpha primes = 1 (mod 2n) lie
below 2^30.  ``reference/ckks_ref.py`` states what the shape means.  The
program's ``CkksContext`` is given ``dnum=`` and ``special_primes=`` only
where the configuration names them; a configuration that names a key
the program's ``CkksContext`` does not take is refused (``SpecError``)
before the program is built.
"""
from __future__ import annotations

import inspect
import time
from collections import deque

import jax
import numpy as np

from harness import serving, spec, traffic as gen
from reference.ckks_ref import CkksRef

# The configuration keys that shape the hybrid key switch (module doc).
KEYSWITCH_KEYS = ("dnum", "special_primes")

# Largest key noise a sound key may read (``CkksRef.key_noise``): sound
# keys read about six standard deviations of the program's sigma 3.2
# error, a key made for another secret, element or gadget about 2^29.
KEY_NOISE_LIMIT = 4096.0


def _arr(ct):
    return np.asarray(ct.c0.data), np.asarray(ct.c1.data)


def keyswitch_shape(config: dict) -> dict:
    """The key-switch keys the configuration names, checked against the
    rules of the module doc (``spec.SpecError`` where one is broken)."""
    named = {k: config[k] for k in KEYSWITCH_KEYS if k in config}
    for k, v in named.items():
        if isinstance(v, bool) or not isinstance(v, int):
            raise spec.SpecError(f"configuration key {k!r}: {v!r} is not "
                                 "a whole number")
    try:
        CkksRef(config["n"], config["levels"], config["ntt_order"], **named)
    except ValueError as e:
        raise spec.SpecError(f"configuration: {e}") from None
    return named


def untaken_keys(build, named) -> list:
    """The keys of ``named`` that ``build`` takes no keyword for."""
    params = inspect.signature(build).parameters.values()
    if any(p.kind is p.VAR_KEYWORD for p in params):
        return []
    return [k for k in named if k not in {
        p.name for p in params
        if p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY)}]


def _keys(evk):
    return (np.stack([np.asarray(p[0].data) for p in evk]),
            np.stack([np.asarray(p[1].data) for p in evk]))


class CkksCell:
    """One CKKS deployment under one traffic mix."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.fhe import linalg
        from repro.fhe.ckks import CkksContext

        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.loop = traffic["loop"]
        self.ks = keyswitch_shape(config)
        untaken = untaken_keys(CkksContext, self.ks)
        if untaken:
            raise spec.SpecError(f"configuration keys {untaken}: the "
                                 "program's CkksContext does not take them")
        ctx = self.ctx = CkksContext(n=config["n"], levels=config["levels"],
                                     scale_bits=config["scale_bits"],
                                     seed=seed, **self.ks)
        plan = self.plan = ctx.plan()
        rng = np.random.default_rng([seed, 1])
        slots = lambda: (rng.uniform(-1, 1, ctx.slots)
                         + 1j * rng.uniform(-1, 1, ctx.slots))
        enc = lambda: ctx.encrypt(ctx.encode(slots()))
        pool = int(traffic["pool"])
        mix = traffic.get("mix", {"multiply": 1.0})
        self.W = self.M = None
        if self.loop == "closed":
            plan.prepare(rotations=(), conjugate=False, warm_jit=False)
        else:
            if "matvec" in mix:
                d_in, d_out = config["matrix"]
                self.W = rng.uniform(-0.5, 0.5, (d_in, d_out))
                self.M = linalg.PtMatrix.encode(ctx, self.W)
            tile, top = config["batch_tile"], config["max_batch"]
            plan.prepare(rotations=tuple(config["rotations"]),
                         conjugate=bool(config["conjugate"]),
                         batch_sizes=tuple(range(tile, top + 1, tile)),
                         matvecs=(self.M,) if self.M is not None else ())
        self.A = [enc() for _ in range(pool)]
        self.B = ([enc() for _ in range(pool)]
                  if {"multiply", "rescale"} & set(mix) else [])
        self.P = ([plan.multiply(a, b) for a, b in zip(self.A, self.B)]
                  if "rescale" in mix else [])
        self.X = ([ctx.encrypt(linalg.encode_vector(
                      ctx, rng.uniform(-1, 1, self.W.shape[0]),
                      self.W.shape[1]))
                   for _ in range(pool)] if self.M is not None else [])
        if self.loop == "closed":
            self._warm_closed()
            self.engine = None
        else:
            self.engine = serving.TimedEngine(
                plan, batch_tile=config["batch_tile"],
                max_batch=config["max_batch"])
        jax.block_until_ready([x for ct in self.A + self.B + self.P + self.X
                               for x in (ct.c0.data, ct.c1.data)])

    # ------------------------------------------------------ requests

    def fhe_request(self, req: gen.Request):
        from repro.fhe.serve import FheRequest
        p = req.pool
        if req.op == "multiply":
            return FheRequest(req.rid, "multiply", self.A[p], other=self.B[p])
        if req.op == "rotate":
            return FheRequest(req.rid, "rotate", self.A[p], r=req.r)
        if req.op == "conjugate":
            return FheRequest(req.rid, "conjugate", self.A[p])
        if req.op == "rescale":
            return FheRequest(req.rid, "rescale", self.P[p])
        if req.op == "matvec":
            return FheRequest(req.rid, "matvec", self.X[p], matrix=self.M)
        raise ValueError(f"unknown CKKS op {req.op!r}")

    # -------------------------------------------------- timed paths

    def _warm_closed(self):
        b = int(self.traffic["batch"])
        idx = np.arange(b) % len(self.A)
        outs = self.plan.multiply_many([self.A[i] for i in idx],
                                       [self.B[i] for i in idx])
        jax.block_until_ready([outs[-1].c0.data, outs[-1].c1.data])

    def run_closed(self, seconds: float, seed: int):
        """Back-to-back ``multiply_many`` calls of ``batch`` pooled pairs,
        at most two in flight, until ``seconds`` have passed; the window
        ends when the last call's outputs are ready."""
        batches = gen.closed_batches(self.traffic, seed)
        keep_rng = np.random.default_rng([seed, 3])
        inflight: deque = deque()
        kept = []
        calls = 0
        ks0 = self.plan.stats["key_switches"]
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            pairs = next(batches)
            outs = self.plan.multiply_many([self.A[i] for i, _ in pairs],
                                           [self.B[j] for _, j in pairs])
            calls += 1
            inflight.append(outs)
            if len(kept) < 2 and keep_rng.random() < 0.25:
                kept.append((pairs, outs))
            if len(inflight) > 2:
                old = inflight.popleft()
                jax.block_until_ready([old[-1].c0.data, old[-1].c1.data])
        jax.block_until_ready([x for outs in inflight for ct in outs
                               for x in (ct.c0.data, ct.c1.data)])
        t_end = time.perf_counter()
        b = int(self.traffic["batch"])
        return {"t0": t0, "t_end": t_end, "wall_s": t_end - t0,
                "attempted": calls * b, "completed": calls * b,
                "calls": calls,
                "plan": {"key_switches": self.plan.stats["key_switches"] - ks0},
                "engine": None, "latencies_s": None}, kept

    # ---------------------------------------------------------- check

    def _ref(self, control: bool) -> CkksRef:
        return CkksRef(self.cfg["n"], self.cfg["levels"],
                       self.cfg["ntt_order"], round_mod_down=not control,
                       **self.ks)

    def expected(self, ref: CkksRef, op: str, pool: int, key, r: int = 0,
                 other: int | None = None, cache: dict | None = None):
        """(c0, c1, scale, basis) the request must produce; ``key(g)``
        gives the (evk_b, evk_a) key of Galois element g, None for the
        relinearisation key."""
        basis = ref.qs
        if op == "multiply":
            a = self.A[pool]
            b = self.B[pool if other is None else other]
            c0, c1 = ref.multiply(_arr(a), _arr(b), basis, key(None))
            return c0, c1, a.scale * b.scale, basis
        if op in ("rotate", "conjugate"):
            a = self.A[pool]
            g = ref.rotation_element(r) if op == "rotate" else 2 * ref.n - 1
            c0, c1 = ref.galois(_arr(a), basis, g, key(g))
            return c0, c1, a.scale, basis
        if op == "rescale":
            p = self.P[pool]
            c0, c1 = ref.rescale(_arr(p), basis)
            return c0, c1, p.scale / basis[-1], basis[:-1]
        if op == "matvec":
            cache = {} if cache is None else cache
            if "diags" not in cache:
                cache["diags"] = ref.encode_diagonals(self.W, self.M.scale,
                                                      basis)
            n1 = ref.bsgs_n1(self.W.shape[0])
            amounts = {j for (_, j) in cache["diags"]} | {
                i * n1 for (i, _) in cache["diags"]}
            keys = {a: key(ref.rotation_element(a)) for a in amounts if a}
            x = self.X[pool]
            c0, c1 = ref.matvec(_arr(x), basis, cache["diags"], n1, keys)
            return c0, c1, x.scale * self.M.scale, basis
        raise ValueError(f"unknown CKKS op {op!r}")

    def check(self, items, control: bool = False):
        """items: [(op, pool, r, other, answer)].  Returns (checked,
        {name: (value, limit)}): the answers that differ from the
        reference, and the largest noise of any key the reference used,
        read against the secret key (``CkksRef.key_noise``).  With
        ``control`` the answers are replaced by the control's, computed
        with the floored division by P."""
        ctx = self.ctx
        ref = self._ref(False)
        ctl = self._ref(True) if control else None
        basis = ref.qs
        keys: dict = {}
        noise: dict = {}

        def key(g):
            if g not in keys:
                evk = _keys(ctx.relin_keys(basis) if g is None
                            else ctx.galois_keys(g, basis))
                noise[g] = ref.key_noise(*evk, ctx._s_coeffs, basis, g)
                keys[g] = evk
            return keys[g]

        cache: dict = {}
        ctl_cache: dict = {}
        mismatched = 0
        for op, pool, r, other, ans in items:
            want = self.expected(ref, op, pool, key, r, other, cache)
            if ctl is not None:
                got = self.expected(ctl, op, pool, key, r, other, ctl_cache)
            else:
                got = (*_arr(ans), ans.scale, tuple(ans.primes))
            same = (np.array_equal(got[0], want[0])
                    and np.array_equal(got[1], want[1])
                    and got[2] == want[2] and tuple(got[3]) == tuple(want[3]))
            mismatched += not same
        return len(items), {
            "answers_mismatched": (mismatched, 0),
            "key_noise_max": (max(noise.values(), default=0.0),
                              KEY_NOISE_LIMIT)}
