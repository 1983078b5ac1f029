"""Continuous-batching CKKS serving engine over the batched EvalPlan
programs.

The paper's headline numbers are *sustained throughput* figures — 531M
NTT/s and 1.63M key-switch ops/s from one deeply pipelined dataflow kept
saturated with back-to-back work, fed by dual coefficient memories in
ping-pong mode (§SRM): while the pipeline consumes one buffer, the host
side fills the other, so the datapath never waits for staging.  This
module is that discipline at the request level.  The scheme layer
already lowers each op to one device program (``fhe.evalplan``) and the
batched ``*_many`` twins run B ciphertexts per dispatch; the engine
keeps those programs FED:

  queue -> group by (op kind, basis) -> pad to the batch tile
        -> ONE ``*_many`` dispatch per group -> unpack per request.

Two drains over the same grouping policy:

  ``run``        the synchronous oracle: collect the whole queue, group,
                 dispatch one group at a time and BLOCK on each before
                 the next — deterministic, host work never overlaps
                 device compute.  Every async answer is pinned bit-exact
                 against it (tests/test_serve_async.py).
  ``run_async``  the ping-pong drain: admit requests from a live
                 arrival stream, dispatch group i+1 while the device is
                 still computing group i, and only then block on group
                 i.  At most two batches are in flight (the paper's
                 double buffer); ``jax.block_until_ready`` on batch i is
                 deferred until batch i+1 has been screened, grouped,
                 padded and dispatched, so host-side admission/stacking
                 overlaps device compute.  Per-request latency
                 (arrival -> drain) is recorded for the SLO bench.

Grouping rules (also the "when batching does not apply" rules):

  * Ops batch only within a kind: multiply with multiply, rescale with
    rescale; rotate and conjugate share the Galois kind — a group may
    MIX rotation amounts (per-ciphertext gather rows + key digits).
    ``matvec`` requests (encrypted BSGS matrix-vector products over a
    ``fhe.linalg.PtMatrix`` pack) form their own kind: each is a
    composite of hoisted-rotation + giant-step dispatches, so the
    group loops per request without tile padding; amortization comes
    from hoisting inside each request, not across requests.
  * Ciphertexts at different bases (levels) NEVER batch — the residue
    stacks have different (k, n) shapes.  Each basis forms its own
    group.  Admission is LEVEL-AWARE but never stalls: the async drain
    takes the queue head's (kind, basis) and collects up to
    ``max_batch`` matching requests from anywhere in the queue; a
    request at a new basis simply opens its own group on a later cycle
    instead of blocking the drain (no head-of-line blocking on shape).
  * Per-request scales ride along host-side (exact per-ciphertext
    tracking), so scale differences never split a group.
  * Schemes NEVER batch together: ``mlkem_*`` requests (FIPS 203
    keygen/encaps/decaps, ciphertext-less ``payload`` dicts riding the
    u16 banks kernels via ``repro.pq.mlkem``) group under a scheme tag
    instead of a residue basis, an ML-KEM request carrying a CKKS
    ciphertext fails alone at screening, and ``_dispatch`` refuses a
    mixed batch outright — one engine drains a mixed CKKS + ML-KEM
    queue, but every dispatch is single-scheme.

Padding: each group is padded up to a multiple of ``batch_tile`` by
repeating its last request (results for pad rows are dropped).  That
bounds the set of jit signatures to multiples of the tile — a fresh
batch size would otherwise recompile the program — and keeps the kernel
grid's batch axis tile-aligned.  Identity rotations (r = 0 mod slots)
short-circuit host-side BEFORE any validation: they need no key
material, no level and no dispatch, exactly like ``EvalPlan.rotate``.

Failure isolation: per-request validation happens at admission, and a
request that fails — mismatched multiply operands, exhausted level, a
poisoned matvec pack raising ANY exception inside its composite — is
recorded in ``stats['failed']`` and never sinks the batch or another
client's answer.

``synthetic_trace`` builds the seeded heavy-traffic workload (mixed op
kinds, mixed levels, optionally Poisson arrivals) the SLO bench and the
demo drive through both drains — offered-load behavior is measured on a
standardized arrival process, not a hand-picked request list.
"""
from __future__ import annotations

import dataclasses
import time
from collections import defaultdict, deque

import numpy as np
import jax

from repro import obs
from repro.fhe import linalg
from repro.fhe.evalplan import (Ciphertext, EvalPlan, check_level,
                                check_same_basis, release_retired)
from repro.kernels import autotune

# op kinds a request may carry; rotate/conjugate share the Galois batch.
# mlkem_* kinds are the ML-KEM scheme's requests: ciphertext-less,
# payload-carrying, and NEVER batched with any CKKS kind (cross-scheme
# groups are rejected — see _screen / _dispatch).
MLKEM_OPS = ("mlkem_keygen", "mlkem_encaps", "mlkem_decaps")
OPS = ("multiply", "rescale", "rotate", "conjugate", "matvec") + MLKEM_OPS

# per-op required payload keys for the ML-KEM request kinds
_MLKEM_PAYLOAD = {
    "mlkem_keygen": ("d", "z"),          # (32,) u8 seeds
    "mlkem_encaps": ("ek", "m"),         # (1184,) key, (32,) randomness
    "mlkem_decaps": ("dk", "ct"),        # (2400,) key, (1088,) ciphertext
}


@dataclasses.dataclass
class FheRequest:
    """One homomorphic op on one ciphertext (plus an operand for
    multiply, a slot amount for rotate, a ``linalg.PtMatrix`` weight
    pack for matvec) — or one ML-KEM op carrying a byte-array
    ``payload`` dict instead of a ciphertext (``ct=None``)."""
    rid: int
    op: str
    ct: Ciphertext | None = None
    other: Ciphertext | None = None      # multiply rhs
    r: int = 0                           # rotate amount
    matrix: "linalg.PtMatrix | None" = None   # matvec weight pack
    payload: dict | None = None          # ML-KEM byte-array inputs

    def __post_init__(self):
        if self.op not in OPS:
            raise ValueError(f"request {self.rid}: unknown op {self.op!r} "
                             f"(expected one of {OPS})")
        if self.op in MLKEM_OPS:
            want = _MLKEM_PAYLOAD[self.op]
            if self.payload is None or any(k not in self.payload
                                           for k in want):
                raise ValueError(
                    f"request {self.rid}: {self.op} needs a payload dict "
                    f"with keys {want}")
            return          # ct deliberately unchecked: screened per-drain
        if self.ct is None:
            raise ValueError(
                f"request {self.rid}: {self.op} needs a ciphertext")
        if self.op == "multiply" and self.other is None:
            raise ValueError(f"request {self.rid}: multiply needs 'other'")
        if self.op == "matvec" and not isinstance(self.matrix, linalg.PtMatrix):
            # a non-PtMatrix would AttributeError inside linalg.matvec
            # before the engine's per-request routing could catch it
            # with a useful message — reject it at construction instead
            raise ValueError(
                f"request {self.rid}: matvec needs 'matrix' (a "
                f"linalg.PtMatrix), got "
                f"{type(self.matrix).__name__ if self.matrix is not None else None}")


def _pad(items: list, tile: int) -> list:
    """Pad to a tile multiple by repeating the last item (dropped on
    unpack); bounds the jit-signature set to tile multiples."""
    want = -len(items) % tile
    return items + [items[-1]] * want


def synthetic_trace(ctx, n_requests: int, *, seed: int = 0,
                    rate: float | None = None, drop_frac: float = 0.25,
                    kinds=("multiply", "rotate", "rescale", "conjugate"),
                    matrix: "linalg.PtMatrix | None" = None):
    """Deterministic synthetic heavy-traffic trace: ``n_requests`` mixed
    requests (op kinds drawn from ``kinds``; ``matvec`` joins the draw
    when a ``matrix`` pack is supplied) over MIXED levels — a seeded
    ``drop_frac`` of the clients arrive one level down, so the trace
    exercises the level-aware admission path, not just one basis.
    Rotation amounts deliberately include negative, identity and
    > slots values.

    Returns ``(requests, arrivals)``: arrivals is ``None`` for a
    backlog trace (everything offered at t=0 — pure throughput), or the
    cumulative seconds of a Poisson process at ``rate`` requests/s.
    Same seed -> same trace, bit for bit; the SLO bench replays one
    trace through both drains and the tests shuffle it to pin
    arrival-order invariance."""
    rng = np.random.default_rng(seed)
    plan = ctx.plan()
    all_kinds = tuple(kinds) + (("matvec",) if matrix is not None else ())
    reqs = []
    for rid in range(n_requests):
        z = rng.uniform(-1, 1, ctx.slots) + 1j * rng.uniform(-1, 1, ctx.slots)
        ct = ctx.encrypt(ctx.encode(z))
        dropped = bool(rng.uniform() < drop_frac)
        if dropped:
            ct = plan.rescale(ct)
        kind = all_kinds[int(rng.integers(len(all_kinds)))]
        if kind == "rescale" and ct.level < 1:
            kind = "rotate"                      # nothing left to drop
        if kind == "matvec" and ct.primes != matrix.basis:
            kind = "rotate"                      # pack valid at ONE basis
        if kind == "multiply":
            z2 = rng.uniform(-1, 1, ctx.slots) + 1j * rng.uniform(-1, 1, ctx.slots)
            other = ctx.encrypt(ctx.encode(z2))
            if dropped:
                other = plan.rescale(other)
            reqs.append(FheRequest(rid, "multiply", ct, other=other))
        elif kind == "rotate":
            r = int(rng.integers(-2, ctx.slots + 3))   # negative/identity/wrap
            reqs.append(FheRequest(rid, "rotate", ct, r=r))
        elif kind == "rescale":
            reqs.append(FheRequest(rid, "rescale", ct))
        elif kind == "matvec":
            reqs.append(FheRequest(rid, "matvec", ct, matrix=matrix))
        else:
            reqs.append(FheRequest(rid, "conjugate", ct))
    arrivals = None
    if rate is not None:
        arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests)).tolist()
    return reqs, arrivals


class CkksServeEngine:
    """Group-and-dispatch batching engine over one prepared ``EvalPlan``.

    ``run`` is the synchronous oracle drain; ``run_async`` is the
    double-buffered continuous-batching drain (same grouping policy,
    same bit-exact answers, host work overlapped with device compute).
    ``max_batch`` caps how many requests one async group may take — it
    bounds the padded-batch jit signatures to multiples of the GROUP
    tile up to ``max_batch``, which is exactly the ``batch_sizes`` a
    caller should warm via ``EvalPlan.prepare``.  On a mesh-sharded plan
    the group tile is ``batch_tile * plan.mesh_devices`` (every device
    gets a full kernel tile per dispatch — see ``__init__``); on a
    single device it degenerates to ``batch_tile`` exactly as before.

    stats (reset per run): ``mode``, ``dispatches`` (request groups
    dispatched), ``batched_ops`` (real requests inside them), ``padded``
    (tile-padding ghost rows), ``identity`` (host-side short-circuits),
    ``failed`` (rid -> message), ``groups`` ((kind, basis-level) ->
    count), ``devices`` / ``per_device_rows`` (mesh width and the batch
    rows each device ran — equal by construction, the saturation
    invariant), ``fresh_traces`` (jit signatures compiled during the
    run — 0 after a complete warm-up), plus the device-work deltas read off
    the plan's cumulative counters: ``program_dispatches`` (jitted
    programs actually launched — a matvec group launches several per
    request), ``key_switches``, ``decomposes``, and ``hoisted_reuse``
    (key switches that shared an already-paid digit decomposition).
    Both drains report ``latency_us`` (p50/p99/mean/max/count request
    latency, arrival -> result drained; an empty dict on a zero-request
    drain so consumers never KeyError), and the async drain adds
    ``max_queue`` (peak pending depth).

    With ``repro.obs`` enabled, every drain additionally records:

      * phase spans ``serve.screen`` / ``serve.group`` /
        ``serve.dispatch`` / ``serve.block`` nested under ``serve.run``
        (the benchmark's trace reduction names device idle gaps by
        them).  Each ``serve.dispatch`` carries ``batch=``, an id unique
        per engine; a matvec group's composites run inside one too.
      * one ``serve.request`` event per resolved request, back-dated
        from its due time (``t0`` + its arrival, ``t0`` for a backlog)
        to its answer being ready (``_block_outs`` returned for its
        group), with args ``rid``, ``kind`` (the op), ``admitted_us``
        and ``dispatched_us`` (offsets from due: admission by the loop,
        return of its group's dispatch) and ``batch``.  A request
        resolved at admission (identity or failed) has all four times at
        its admission and ``batch`` None; a matvec that fails inside its
        group is ready when the group returns.  Read by the benchmark's
        ``serve.admit_lag_p99_ms``, ``serve.queue_ms`` and
        ``serve.inflight_ms``, and by its clock module, which lays
        [due, dispatched) over the device's idle stretches.
      * counters mirroring ``stats`` (``serve.*``).

    Disabled (the default), each probe is one flag check per drain
    cycle, and no per-request record is kept."""

    def __init__(self, plan: EvalPlan, batch_tile: int | None = None,
                 max_batch: int | None = None):
        # a mesh-sharded plan splits each batched dispatch over its "b"
        # axis, so the engine sizes groups to batch_tile * devices: every
        # device then sees a full batch_tile of rows per dispatch (the
        # device-saturation analog of the paper's replicated-PE scaling)
        # and the plan's own shard-padding never fires on engine traffic
        self.devices = getattr(plan, "mesh_devices", 1)
        if batch_tile is None:
            # autotuned default (pin > cache > 8): the admission batch is
            # open-ended, so resolve against a representative group of 32
            # — against the PER-SHARD batch on a sharded plan (shards=),
            # because that is the kernel grid each device actually runs
            k = len(plan.ctx.qs) if hasattr(plan.ctx, "qs") else 2
            batch_tile = autotune.resolve_tile("serve_batch", k, plan.n, 32,
                                               shards=self.devices)
        if batch_tile < 1:
            raise ValueError(f"batch_tile must be >= 1, got {batch_tile}")
        self.plan = plan
        self.batch_tile = batch_tile
        self.group_tile = batch_tile * self.devices
        self.max_batch = (max_batch if max_batch is not None
                          else 4 * self.group_tile)
        if self.max_batch < self.group_tile:
            raise ValueError(f"max_batch {self.max_batch} < batch_tile "
                             f"{batch_tile} x {self.devices} device(s)")
        self.stats: dict = {}
        self._batches = 0       # serve.dispatch batch ids
        # rid -> [due, admitted, dispatched, batch] while a traced drain
        # runs, None otherwise
        self._life: dict | None = None

    # ------------------------------------------------------------ policy

    @staticmethod
    def _kind(req: FheRequest) -> str:
        return "galois" if req.op in ("rotate", "conjugate") else req.op

    @staticmethod
    def _basis(req: FheRequest):
        """The shape/scheme component of the group key: CKKS requests
        group by residue basis, ML-KEM requests (no ciphertext) by a
        scheme tag — so cross-scheme requests can never share a group
        even if a kind ever collided."""
        return (req.ct.primes if req.ct is not None
                else ("mlkem", req.op))

    def _screen(self, req: FheRequest, done: dict, failed: dict) -> bool:
        """Admission-time screening for one request; returns True if it
        should queue for dispatch.  Identity rotations (r = 0 mod
        slots) short-circuit FIRST — before any level check — because
        they need no key material and no dispatch: a fully exhausted
        ciphertext can still be identity-rotated (the level check used
        to run first and failed such requests; pinned in
        tests/test_serve_fhe.py).  Validation failures land in
        ``failed`` so a bad request never aborts the batch."""
        if req.op in MLKEM_OPS:
            if req.ct is not None:
                # cross-scheme guard: an ML-KEM request smuggling a CKKS
                # ciphertext fails ALONE — it must never open (or join)
                # a batch whose kernels expect the other scheme's lanes
                failed[req.rid] = (
                    f"request {req.rid}: {req.op} is an ML-KEM op and "
                    f"cannot carry a CKKS ciphertext — cross-scheme "
                    f"requests never batch together")
                return False
            return True
        if req.op == "rotate" and req.r % (self.plan.n // 2) == 0:
            ct = req.ct
            done[req.rid] = Ciphertext(ct.c0, ct.c1, ct.scale)
            return False
        try:
            if req.op == "multiply":
                check_same_basis("multiply", req.ct, req.other)
                check_level("multiply", req.ct)
            elif req.op == "rescale":
                check_level("rescale", req.ct, need=1)
            else:
                # (matvec's own checks — pack basis validity, empty
                # pack — fire inside the per-request dispatch loop,
                # which routes them into ``failed`` the same way;
                # ONE source of truth lives in linalg.matvec)
                check_level(req.op, req.ct)
        except ValueError as e:
            failed[req.rid] = str(e)
            return False
        return True

    def _group(self, requests):
        """(kind, basis) -> request list, for the synchronous drain."""
        groups: dict = defaultdict(list)
        done: dict[int, Ciphertext] = {}
        failed: dict[int, str] = {}
        with obs.span("serve.screen", n=len(requests)):
            admitted = [req for req in requests
                        if self._screen(req, done, failed)]
        with obs.span("serve.group", n=len(admitted)):
            for req in admitted:
                groups[(self._kind(req), self._basis(req))].append(req)
        return groups, done, failed

    def _g_of(self, req: FheRequest) -> int:
        return (2 * self.plan.n - 1 if req.op == "conjugate"
                else self.plan.rotation_group_element(req.r))

    def _next_batch(self) -> int:
        self._batches += 1
        return self._batches

    def _dispatch(self, kind: str, reqs: list,
                  batch: int | None = None) -> list:
        plan = self.plan
        schemes = {"mlkem" if r.op in MLKEM_OPS else "ckks" for r in reqs}
        if len(schemes) > 1:
            # belt and braces under the grouping policy: the (kind,
            # basis) key already separates schemes, so reaching here
            # means a caller bypassed grouping — refuse loudly rather
            # than feed one scheme's rows to the other's kernels
            raise ValueError(
                f"_dispatch: cross-scheme batch {sorted(schemes)} — "
                f"CKKS and ML-KEM requests never batch together")
        with obs.span("serve.dispatch", kind=kind, n=len(reqs), batch=batch):
            reqs = _pad(reqs, self.group_tile)
            if kind in MLKEM_OPS:
                return self._mlkem_dispatch(kind, reqs)
            if kind == "multiply":
                outs = plan.multiply_many([r.ct for r in reqs],
                                          [r.other for r in reqs])
            elif kind == "rescale":
                outs = plan.rescale_many([r.ct for r in reqs])
            else:                        # galois: may mix g per request
                outs = plan.galois_ks_many([r.ct for r in reqs],
                                           [self._g_of(r) for r in reqs])
        return outs

    @staticmethod
    def _mlkem_dispatch(kind: str, reqs: list) -> list:
        """One batched ML-KEM dispatch for a (padded) same-op group: the
        payload rows stack into (b, …) u8 arrays and ride the pq.mlkem
        batch entry points — whose polynomial arithmetic runs through
        the SAME banks kernels as the CKKS groups, on the u16 ring.
        Per-request results: keygen -> (ek, dk), encaps -> (K, ct),
        decaps -> K."""
        from repro.pq import mlkem      # lazy: pq is optional for CKKS use

        def rows(key):
            return np.stack([np.asarray(r.payload[key], dtype=np.uint8)
                             for r in reqs])

        if kind == "mlkem_keygen":
            ek, dk = mlkem.keygen_batch(rows("d"), rows("z"))
            return [(ek[i], dk[i]) for i in range(len(reqs))]
        if kind == "mlkem_encaps":
            key, ct = mlkem.encaps_batch(rows("ek"), rows("m"))
            return [(key[i], ct[i]) for i in range(len(reqs))]
        key = mlkem.decaps_batch(rows("dk"), rows("ct"))
        return [key[i] for i in range(len(reqs))]

    @staticmethod
    def _block_outs(outs: list) -> None:
        """Synchronize a drained group: CKKS outs block on their device
        stacks; ML-KEM outs are host numpy already (their device work
        was synchronized inside the batched kernel calls)."""
        with obs.span("serve.block", n=len(outs)):
            jax.block_until_ready([x for ct in outs
                                   if isinstance(ct, Ciphertext)
                                   for x in (ct.c0.data, ct.c1.data)])

    def _matvec_group(self, reqs: list, failed: dict,
                      batch: int | None = None):
        """Per-request matvec composites (no tile padding).  ANY
        exception a request raises — the documented ValueErrors
        (basis-validity, empty pack) but also a poisoned pack's
        TypeError/AttributeError deep inside ``linalg.matvec`` — fails
        that request ALONE: before this routing, one wrong-shaped
        ``PtMatrix`` sank the whole batch and discarded every other
        client's answer."""
        kept, outs = [], []
        with obs.span("serve.dispatch", kind="matvec", n=len(reqs),
                      batch=batch):
            for req in reqs:
                try:
                    outs.append(linalg.matvec(self.plan, req.matrix, req.ct))
                    kept.append(req)
                except ValueError as e:
                    failed[req.rid] = str(e)
                except Exception as e:   # noqa: BLE001 — isolate the batch
                    failed[req.rid] = f"{type(e).__name__}: {e}"
        return kept, outs

    # -------------------------------------------------------- lifecycle
    # Only called while a traced drain keeps ``self._life``: once per
    # admission cycle, dispatched group and drained group.

    @staticmethod
    def _emit(rid: int, op: str, due: float, admitted: float,
              dispatched: float, ready: float, batch) -> None:
        obs.record("serve.request", due, ready, rid=rid, kind=op,
                   admitted_us=(admitted - due) * 1e6,
                   dispatched_us=(dispatched - due) * 1e6, batch=batch)

    def _admit(self, arrived, t0: float, t_adm: float, resolved) -> None:
        """Stamp one admission cycle's ``(arrival, request)`` pairs; a
        request already in ``resolved`` is over at its admission."""
        for a, req in arrived:
            if req.rid in resolved:
                self._emit(req.rid, req.op, t_adm, t_adm, t_adm, t_adm, None)
            else:
                self._life[req.rid] = [t0 + a, t_adm]

    def _dispatched(self, taken: list, failed: dict, batch: int) -> None:
        """Stamp a group's dispatch; its requests that failed inside the
        group are ready at the same moment."""
        t = time.perf_counter()
        for req in taken:
            rec = self._life[req.rid]
            rec += (t, batch)
            if req.rid in failed:
                self._emit(req.rid, req.op, *rec[:3], t, batch)
                del self._life[req.rid]

    def _ready(self, reqs: list) -> None:
        t = time.perf_counter()
        for req in reqs:
            due, adm, disp, batch = self._life.pop(req.rid)
            self._emit(req.rid, req.op, due, adm, disp, t, batch)

    # ------------------------------------------------------- accounting

    def _init_stats(self, mode: str, failed: dict) -> dict:
        stats = self.stats = {
            "mode": mode, "dispatches": 0, "batched_ops": 0, "padded": 0,
            "identity": 0, "failed": failed, "groups": {},
            "devices": self.devices,
            "per_device_rows": [0] * self.devices}
        return stats

    def _account_group(self, stats, kind: str, reqs: list):
        stats["dispatches"] += 1
        stats["batched_ops"] += len(reqs)
        if kind != "matvec":                 # matvec never tile-pads
            pad = -len(reqs) % self.group_tile
            stats["padded"] += pad
            # per-device dispatch accounting: a group-tile-padded batch
            # splits evenly over the mesh's "b" axis, so each device ran
            # exactly rows/devices of it — the saturation evidence the
            # scaling bench asserts on (every device equally loaded)
            rows = (len(reqs) + pad) // self.devices
            for d in range(self.devices):
                stats["per_device_rows"][d] += rows
        key = (f"{kind}@mlkem" if kind in MLKEM_OPS
               else f"{kind}@L{len(reqs[0].ct.primes) - 1}")
        stats["groups"][key] = stats["groups"].get(key, 0) + len(reqs)

    @staticmethod
    def _latency_summary(arr_t: dict, done_t: dict) -> dict:
        """p50/p99/mean/max/count over per-request arrival -> drained
        latencies (µs).  BOTH drains report this now (the sync drain
        historically did not — serve.py S1 parity), and a zero-request
        input yields an empty-but-present dict so consumers indexing
        ``stats['latency_us']`` never KeyError."""
        lats = [(done_t[rid] - arr_t.get(rid, 0.0)) * 1e6 for rid in done_t]
        if not lats:
            return {}
        q = np.percentile(lats, (50, 99))
        return {
            "p50": float(q[0]), "p99": float(q[1]),
            "mean": float(np.mean(lats)), "max": float(np.max(lats)),
            "count": len(lats)}

    def _finish_stats(self, stats, before, traces_before, t0):
        # device-work accounting from the plan's cumulative counters:
        # program_dispatches is the true jitted-program count (a matvec
        # group launches several per request), and hoisted_reuse is the
        # key switches that shared an already-paid digit decomposition
        # — the amortization the hoisting subsystem exists to buy
        for c in ("dispatches", "key_switches", "decomposes"):
            delta = self.plan.stats[c] - before.get(c, 0)
            stats["program_dispatches" if c == "dispatches" else c] = delta
        stats["hoisted_reuse"] = stats["key_switches"] - stats["decomposes"]
        stats["fresh_traces"] = self.plan.trace_count() - traces_before
        stats["wall_s"] = time.perf_counter() - t0
        if obs.enabled():
            # mirror the drain's accounting into the metrics registry —
            # the stats dict stays the source of truth for tests, the
            # registry accumulates across drains for the snapshot artifact
            for c in ("dispatches", "batched_ops", "padded", "identity",
                      "program_dispatches", "key_switches", "decomposes",
                      "hoisted_reuse", "fresh_traces"):
                obs.counter_add(f"serve.{c}", stats[c])
            obs.counter_add("serve.failed", len(stats["failed"]))
            obs.counter_add("serve.drains")
        # everything is drained now, so parked donated stacks (see
        # evalplan.retire_donated) can be dropped without blocking
        release_retired()

    @staticmethod
    def _check_rids(requests):
        rids = [r.rid for r in requests]
        if len(set(rids)) != len(rids):
            raise ValueError("duplicate request ids")

    # ----------------------------------------------- synchronous drain

    def run(self, requests: list[FheRequest]) -> dict[int, Ciphertext]:
        """The synchronous oracle drain: answer every valid request with
        one ``*_many`` dispatch per (kind, basis) group, largest group
        first, BLOCKING on each group before touching the next (a
        request/response server answers group i before staging group
        i+1 — the baseline ``run_async`` is benched against, and the
        bit-exactness oracle it is pinned against).  Invalid requests
        are dropped from the result and reported in ``stats['failed']``
        (rid -> message) — a bad request never sinks the batch."""
        self._check_rids(requests)
        t0 = time.perf_counter()
        before = dict(self.plan.stats)
        traces_before = self.plan.trace_count()
        self._life = {} if obs.enabled() else None
        with obs.span("serve.run", mode="sync", n=len(requests)):
            groups, out, failed = self._group(requests)
            stats = self._init_stats("sync", failed)
            stats["identity"] = len(out)
            # identity short-circuits and admission failures resolve at
            # screen time; a backlog drain's arrivals are all t0, so
            # latency here is time-into-the-drain (parity with run_async
            # on a backlog trace — serve.py S1)
            now = time.perf_counter() - t0
            done_t = {rid: now for rid in (*out, *failed)}
            if self._life is not None:
                self._admit([(0.0, r) for r in requests], t0, t0 + now,
                            done_t)
            for (kind, basis), reqs in sorted(
                    groups.items(), key=lambda kv: -len(kv[1])):
                if kind == "galois":
                    # canonical g order: results route by rid anyway,
                    # and a sorted batch makes the g-pattern (and so the
                    # plan's stacked batch-key cache key) independent of
                    # arrival order — arrival-ordered patterns would
                    # miss that cache almost every dispatch
                    reqs = sorted(reqs, key=self._g_of)
                taken, batch = reqs, self._next_batch()
                if kind == "matvec":
                    reqs, outs = self._matvec_group(reqs, failed, batch)
                else:
                    outs = self._dispatch(kind, reqs, batch)
                if self._life is not None:
                    self._dispatched(taken, failed, batch)
                if not reqs:
                    continue       # every matvec failed: nothing dispatched
                # the drain discipline: fully synchronize this group
                # before staging the next (run_async defers exactly this)
                self._block_outs(outs)
                if self._life is not None:
                    self._ready(reqs)
                done = time.perf_counter() - t0
                for req, ct in zip(reqs, outs):  # zip drops pad rows
                    out[req.rid] = ct
                    done_t[req.rid] = done
                self._account_group(stats, kind, reqs)
            now = time.perf_counter() - t0
            for rid in failed:     # matvec failures surface mid-drain
                done_t.setdefault(rid, now)
            stats["latency_us"] = self._latency_summary({}, done_t)
        self._life = None
        self._finish_stats(stats, before, traces_before, t0)
        return out

    # ------------------------------------------- continuous-batch drain

    def _take_group(self, pending: deque):
        """Level-aware admission without stalling: the queue head fixes
        (kind, basis) and up to ``max_batch`` matching requests join it
        from anywhere in the queue (FIFO within the group); everything
        else stays queued for a later cycle.  The head always
        dispatches, so a request at a new basis opens a group instead
        of blocking the drain."""
        with obs.span("serve.group", pending=len(pending)):
            head = pending[0]
            key = (self._kind(head), self._basis(head))
            take: list = []
            rest: deque = deque()
            for req in pending:
                if (len(take) < self.max_batch
                        and (self._kind(req), self._basis(req)) == key):
                    take.append(req)
                else:
                    rest.append(req)
            pending.clear()
            pending.extend(rest)
        return key[0], take

    def _drain(self, batch, out, done_t, t0, stats):
        """Block on an in-flight batch and deliver its answers."""
        kind, reqs, outs = batch
        self._block_outs(outs)
        if self._life is not None:
            self._ready(reqs)
        done = time.perf_counter() - t0
        for req, ct in zip(reqs, outs):          # zip drops pad rows
            out[req.rid] = ct
            done_t[req.rid] = done
        self._account_group(stats, kind, reqs)

    def run_async(self, requests: list[FheRequest],
                  arrivals: list[float] | None = None) -> dict[int, Ciphertext]:
        """The ping-pong drain: double-buffered continuous batching over
        a live queue.  Each cycle admits every arrived request (screened
        at admission — identity short-circuits and validation failures
        resolve immediately), takes the queue head's (kind, basis)
        group, DISPATCHES it, and only then blocks on the *previous*
        batch: at most two batches are in flight, and the host-side
        screening/grouping/stacking of batch i+1 overlaps the device
        compute of batch i (the §SRM dual-coefficient-memory ping-pong,
        lifted to request batches).

        ``arrivals`` (seconds, per request) simulates an offered-load
        stream: requests are admitted only once their arrival time has
        passed, and per-request latency (arrival -> batch drained) is
        reported in ``stats['latency_us']``.  ``None`` means a backlog
        (everything available at t=0 — the pure-throughput mode).

        Answers are bit-exact vs ``run`` regardless of arrival order:
        grouping only changes which dispatch a request rides, and every
        ``*_many`` program is elementwise per batch row (pinned in
        tests/test_serve_async.py)."""
        self._check_rids(requests)
        n = len(requests)
        if arrivals is not None and len(arrivals) != n:
            raise ValueError(f"run_async: {n} requests vs "
                             f"{len(arrivals)} arrivals")
        t0 = time.perf_counter()
        before = dict(self.plan.stats)
        traces_before = self.plan.trace_count()
        out: dict[int, Ciphertext] = {}
        failed: dict[int, str] = {}
        stats = self._init_stats("async", failed)
        stats["max_queue"] = 0
        if arrivals is None:
            sched = [(0.0, req) for req in requests]
        else:
            sched = sorted(zip(arrivals, requests), key=lambda ar: ar[0])
        arr_t = {req.rid: a for a, req in sched}
        done_t: dict[int, float] = {}
        pending: deque = deque()
        inflight = None                 # (kind, reqs, outs) — ONE batch
        i = 0                           # next unadmitted arrival
        self._life = {} if obs.enabled() else None

        run_span = obs.span("serve.run", mode="async", n=n)
        with run_span:
            while i < n or pending or inflight:
                now = time.perf_counter() - t0
                if i < n and sched[i][0] <= now:
                    i0 = i
                    with obs.span("serve.screen"):
                        while i < n and sched[i][0] <= now:
                            a, req = sched[i]
                            i += 1
                            if self._screen(req, out, failed):
                                pending.append(req)
                            else:       # resolved at admission
                                done_t[req.rid] = now
                                if req.rid in out:
                                    stats["identity"] += 1
                    if self._life is not None:
                        self._admit(sched[i0:i], t0, t0 + now, done_t)
                stats["max_queue"] = max(stats["max_queue"], len(pending))
                if pending:
                    kind, reqs = self._take_group(pending)
                    if kind == "galois":
                        reqs = sorted(reqs, key=self._g_of)  # canonical g
                    taken, batch = reqs, self._next_batch()
                    if kind == "matvec":
                        reqs, outs = self._matvec_group(reqs, failed, batch)
                    else:
                        outs = self._dispatch(kind, reqs, batch)
                    if self._life is not None:
                        self._dispatched(taken, failed, batch)
                    # ping-pong: the new batch is in flight BEFORE we
                    # block on the old one — its compute hides this
                    # cycle's host screening/stacking, the next cycle's
                    # hides ours
                    if reqs:
                        if inflight is not None:
                            self._drain(inflight, out, done_t, t0, stats)
                        inflight = (kind, reqs, outs)
                elif inflight is not None:
                    self._drain(inflight, out, done_t, t0, stats)
                    inflight = None
                else:
                    # idle: nothing pending, nothing in flight — sleep
                    # up to the next arrival (short naps keep admission
                    # responsive)
                    wait = sched[i][0] - (time.perf_counter() - t0)
                    if wait > 0:
                        time.sleep(min(wait, 5e-4))
            stats["latency_us"] = self._latency_summary(arr_t, done_t)
        self._life = None
        self._finish_stats(stats, before, traces_before, t0)
        return out
