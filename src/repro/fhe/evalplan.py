"""Device-resident CKKS evaluation plans (paper Fig 1 / Fig 22).

The paper's architectural claim is that *every* ciphertext ring op —
NTT, iNTT, dyadic MM/MA, base extension, RNS floor — lives on the
SCE-NTT side, with only keygen/encode/decode on the CMOS host, and that
key-switch throughput comes from running the whole op as one deeply
pipelined dataflow rather than per-stage host round trips.  An
``EvalPlan`` is that boundary in code: it precomputes, per
``(primes, n)`` basis,

  * the stacked twiddle tables the bank kernels consume (TablePack for
    single-kernel rings, FourStepPack + scalar pack past
    ``ops.FOURSTEP_MIN_N``),
  * stacked evaluation / Galois key tensors — ``(digits, k+alpha, n)``
    device arrays instead of Python lists of RnsPoly pairs,
  * for grouped digits (several primes a digit) and alpha > 1 special
    primes, the centred base-conversion tables of the mod-up and the
    mod-down (``keyswitch_tables``),
  * NTT-domain Galois gather rows (``core.params.galois_eval_perm``)
    plus the coefficient-domain index/sign tables, and
  * the per-prime ``pinv`` scalar columns of every mod-down,

and then lowers each hot scheme op to ONE jitted device program over
raw (k, n) residue stacks:

  multiply   -> ``multiply_banks``  (tensor + fused batched_keyswitch)
  rescale    -> ``rescale_banks``   (fused mod_down_banks, both halves
                                     batched through one pipeline)
  rotate/conjugate -> ``galois_ks_banks`` (one NTT-domain gather
                                     + fused batched_keyswitch)
  R rotations of one ct -> ``hoisted_rotations_banks`` (decompose-once:
                                     one ``decompose_banks``, R digit
                                     gathers + R key inner products +
                                     one fused mod-down — the slot-
                                     linalg primitive of ``fhe.linalg``)

Each program also has a ciphertext-batched ``*_many`` twin
(``multiply_many_banks`` / ``rescale_many_banks`` /
``galois_ks_many_banks``) taking (B, k, n) leading-batch stacks: B
independent ciphertexts at the same basis ride ONE dispatch, with the
batch folded into the same (prime, batch_tile) kernel grids — the
throughput layer a serving loop runs on (``fhe.serve``).  A Galois
batch carries a per-ciphertext gather row and per-ciphertext key
digits, so one dispatch can mix rotation amounts.  Batching never
crosses bases: ciphertexts at different levels shape-mismatch at the
kernel grid, so the engine (and ``EvalPlan.*_many``) group by basis
first and mixed-basis batches raise ``ValueError``.

``RnsPoly`` stays as a thin (data, primes, is_ntt) view around the
stacks; no Python loop over primes, digits or rows survives in any of
these paths.  The host-orchestrated ``fhe.keyswitch`` module remains as
the bit-exact oracle the tests pin against.

Scale-out: ``EvalPlan(mesh=...)`` shards the batched programs over a
device mesh.  A mesh axis named "b" splits the ciphertext batch axis
(and the hoisted program's rotation axis) across devices via
``shard_map`` twins of the ``*_many`` programs
(``sharded_many_programs``) — per-shard compute only, no collectives,
tables/keys replicated; a mesh axis named "k" commits the RNS prime
axis of the residue stacks to the mesh (``NamedSharding``) and lets
XLA's SPMD partitioner insert exactly the collectives the
decompose/mod-down cross-prime reductions genuinely need.  Either way
the outputs stay bit-identical to the unsharded programs (integer
modular arithmetic has no association-order effects), pinned by
tests/test_sharded_eval.py.

Key generation is host-side by design (the CMOS coprocessor role): the
``CkksContext`` makes each key once over the top-level basis, a lower
basis takes its rows, and the plan keeps only the stacked device tensors
per basis.  The plan takes every key-switch shape the context has;
meshes take single-prime digits and one special prime only.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro import compat, obs
from repro.core.modmath import addmod, mulmod_barrett
from repro.core.params import galois_eval_perm
from repro.fhe import batched as FB
from repro.fhe import rns
from repro.fhe.batched import batched_keyswitch, mod_down_banks
from repro.fhe.rns import RnsPoly
from repro.kernels import ops


@dataclasses.dataclass
class Ciphertext:
    c0: RnsPoly
    c1: RnsPoly
    scale: float

    @property
    def primes(self):
        return self.c0.primes

    @property
    def level(self) -> int:
        return len(self.primes) - 1


# ------------------------------------------------------- scheme-API checks
#
# Public scheme entry points validate with explicit ``ValueError``s, not
# ``assert`` — asserts are stripped under ``python -O`` and a basis or
# scale mismatch would then produce silently wrong ciphertexts.

def _ct_desc(ct: Ciphertext) -> str:
    return f"primes={ct.primes} (level {ct.level}, scale {ct.scale:g})"


def check_same_basis(op: str, a: Ciphertext, b: Ciphertext,
                     check_scale: bool = False):
    """Raise ``ValueError`` (never assert) when two operands disagree on
    basis — or on scale, for ops like ``add`` that require it."""
    if a.primes != b.primes:
        raise ValueError(
            f"{op}: operand bases differ — lhs {_ct_desc(a)} vs rhs "
            f"{_ct_desc(b)}; rescale / level-align both operands first "
            "(mixed bases never combine or batch)")
    if check_scale and abs(a.scale - b.scale) > 1e-9 * abs(a.scale):
        raise ValueError(
            f"{op}: operand scales differ — lhs {_ct_desc(a)} vs rhs "
            f"{_ct_desc(b)}; rescale or scale-match the operands first")


def check_level(op: str, ct: Ciphertext, need: int = 0):
    """Explicit level-exhaustion check: ``rescale`` needs a modulus to
    drop (need=1) and every op needs a non-empty basis, otherwise the
    failure surfaces as an opaque shape error deep in the kernel stack."""
    if ct.level < need:
        raise ValueError(
            f"{op}: prime chain exhausted — ciphertext has "
            f"{len(ct.primes)} modulus(es) left ({_ct_desc(ct)}) but "
            f"{op} needs level >= {need}; build the CkksContext with "
            "more levels for deeper circuits")


# ------------------------------------------------- jitted device programs
#
# Each program takes its tables/keys as explicit pytree arguments, so one
# trace is shared by every plan with the same (k, n) signature; the
# ``use_pallas``/``tile`` dispatch knobs are static.  ``tile=None``
# resolves per entry point through ``kernels.autotune`` at trace time
# (deterministic: pin > cache > default, never a measurement), so one
# trace per (B, k, n) signature still holds.

@functools.partial(jax.jit, static_argnames=("use_pallas", "tile"))
def multiply_banks(a0, a1, b0, b1, evk_b, evk_a, t, fsp=None, *,
                   use_pallas: bool | None = None, tile: int | None = None):
    """Ciphertext tensor + relinearization as one device program.

    a0/a1/b0/b1: (k, n) u32 NTT-form halves over the k-prime basis;
    evk_b/evk_a: (k, k+1, n) stacked relin key digits; t (+ optional
    fsp) the basis+special tables.  Returns the (c0, c1) stacks."""
    k = a0.shape[0]
    q = t["qs"][:k, None]
    mu = t["mu"][:k, None]
    d0 = mulmod_barrett(a0, b0, q, mu)
    d1 = addmod(mulmod_barrett(a0, b1, q, mu),
                mulmod_barrett(a1, b0, q, mu), q)
    d2 = mulmod_barrett(a1, b1, q, mu)
    ks0, ks1 = batched_keyswitch(d2[:, None], evk_b, evk_a, t, fsp=fsp,
                                 use_pallas=use_pallas, tile=tile)
    return addmod(d0, ks0[:, 0], q), addmod(d1, ks1[:, 0], q)


@functools.partial(jax.jit, static_argnames=("use_pallas", "tile"))
def rescale_banks(c0, c1, t, fsp=None, *, use_pallas: bool | None = None,
                  tile: int | None = None):
    """Rescale by the last basis prime: both ciphertext halves ride one
    fused ``mod_down_banks`` pipeline as a batch of two.  t's basis is
    the ciphertext basis itself (its last prime is the one dropped)."""
    acc = jnp.stack([c0, c1], axis=1)                 # (k+1, 2, n)
    out = mod_down_banks(acc, t, fsp=fsp, use_pallas=use_pallas, tile=tile)
    return out[:, 0], out[:, 1]


@functools.partial(jax.jit, static_argnames=("use_pallas", "tile"))
def galois_ks_banks(c0, c1, idx, evk_b, evk_a, t, fsp=None, *,
                    use_pallas: bool | None = None, tile: int | None = None):
    """Slot rotation / conjugation: NTT-domain gather on both halves
    (one ``galois_banks`` gather each — no iNTT/NTT round trip), then the
    fused key switch of the permuted c1 under the Galois key."""
    k = c0.shape[0]
    q = t["qs"][:k, None]
    c0g = ops.galois_banks(c0, idx)
    c1g = ops.galois_banks(c1, idx)
    ks0, ks1 = batched_keyswitch(c1g[:, None], evk_b, evk_a, t, fsp=fsp,
                                 use_pallas=use_pallas, tile=tile)
    return addmod(c0g, ks0[:, 0], q), ks1[:, 0]


# ------------------------------------- ciphertext-batched device programs
#
# The ``*_many`` twins take (B, k, n) leading-batch stacks — B
# independent ciphertexts at the same basis — and fold the batch into
# the same fused pipelines, so a serving loop pays ONE dispatch (and one
# jit cache entry per (B, k, n) signature) for the whole group.  Every
# stage is elementwise per batch row, so the results are bit-identical
# to a Python loop of the single-ciphertext programs above (pinned in
# tests/test_batched_eval.py).

# donation policy for the hot batched programs below: reusing the two
# (B, k, n) input allocations for the two outputs halves the live
# batch buffers while the serve engine keeps two batches in flight —
# but only OFF the CPU backend.  On CPU PJRT the aliasing constraint
# measurably pessimizes the thunk schedule (batch-32 multiply runs
# ~19% slower per op — enough to lose the batched-amortization CI
# gate), and host memory is not the scarce resource there.  Callers
# still route the stacks through ``retire_donated`` unconditionally:
# a no-op cost when nothing is donated, and the required keepalive
# when something is.
_DONATE_BANKS = () if jax.default_backend() == "cpu" else (0, 1)


# The batched program BODIES are plain functions, shared by two jitted
# skins: the module-level single-device programs below, and the
# per-mesh ``shard_map`` twins ``sharded_many_programs`` builds (each
# shard runs the identical pipeline on its local batch rows, so the
# twins are bit-identical by construction).

def _multiply_many_impl(a0, a1, b0, b1, evk_b, evk_a, t, fsp=None,
                        use_pallas: bool | None = None,
                        tile: int | None = None):
    k = a0.shape[1]
    q = t["qs"][:k][None, :, None]
    mu = t["mu"][:k][None, :, None]
    d0 = mulmod_barrett(a0, b0, q, mu)
    d1 = addmod(mulmod_barrett(a0, b1, q, mu),
                mulmod_barrett(a1, b0, q, mu), q)
    d2 = mulmod_barrett(a1, b1, q, mu)
    ks0, ks1 = batched_keyswitch(d2.swapaxes(0, 1), evk_b, evk_a, t, fsp=fsp,
                                 use_pallas=use_pallas, tile=tile)
    return (addmod(d0, ks0.swapaxes(0, 1), q),
            addmod(d1, ks1.swapaxes(0, 1), q))


@functools.partial(jax.jit, static_argnames=("use_pallas", "tile"),
                   donate_argnums=_DONATE_BANKS)
def multiply_many_banks(a0, a1, b0, b1, evk_b, evk_a, t, fsp=None, *,
                        use_pallas: bool | None = None, tile: int | None = None):
    """B ciphertext tensor products + relinearization, one program.

    a0/a1/b0/b1: (B, k, n) u32 NTT-form halves; evk_b/evk_a: (k, k+1, n)
    relin key digits shared by the batch.  Returns (B, k, n) stacks.

    a0/a1 are DONATED off-CPU (``_DONATE_BANKS``): the callers
    (``EvalPlan.multiply_many``) pass freshly ``jnp.stack``-ed copies,
    never a live ciphertext's buffer, so XLA reuses the two (B, k, n)
    input allocations for the two (B, k, n) outputs instead of
    allocating new HBM per dispatch — the continuous-batching serve
    loop keeps two batches in flight and would otherwise hold four
    ciphertext-batch buffers live.  Caveat: the caller must keep the
    donated stacks referenced until this program has EXECUTED
    (``retire_donated``) — PJRT invalidates a donated handle at
    dispatch, and destroying it while the program is still pending
    blocks the host on the whole dependency chain."""
    return _multiply_many_impl(a0, a1, b0, b1, evk_b, evk_a, t, fsp,
                               use_pallas, tile)


def _rescale_many_impl(c0, c1, t, fsp=None, use_pallas: bool | None = None,
                       tile: int | None = None):
    B, kp1, n = c0.shape
    acc = jnp.stack([c0, c1], axis=1)                  # (B, 2, k+1, n)
    acc = acc.reshape(2 * B, kp1, n).swapaxes(0, 1)    # (k+1, 2B, n)
    out = mod_down_banks(acc, t, fsp=fsp, use_pallas=use_pallas, tile=tile)
    out = out.swapaxes(0, 1).reshape(B, 2, kp1 - 1, n)
    return out[:, 0], out[:, 1]


@functools.partial(jax.jit, static_argnames=("use_pallas", "tile"))
def rescale_many_banks(c0, c1, t, fsp=None, *, use_pallas: bool | None = None,
                       tile: int | None = None):
    """Rescale B ciphertexts by the last basis prime: all 2B halves ride
    one fused ``mod_down_banks`` pipeline.  c0/c1: (B, k+1, n).

    No buffer donation here: the outputs are (B, k, n) — one prime row
    smaller than the (B, k+1, n) inputs — so XLA could never alias them
    and donation would only emit unusable-donation warnings."""
    return _rescale_many_impl(c0, c1, t, fsp, use_pallas, tile)


@functools.partial(jax.jit, static_argnames=("use_pallas", "tile"))
def hoisted_rotations_banks(c0, c1, idx, evk_b, evk_a, t, fsp=None, *,
                            use_pallas: bool | None = None, tile: int | None = None):
    """R rotations of ONE ciphertext as one device program, with the
    expensive key-switch front half HOISTED: the RNS digit decomposition
    of c1 (iNTT units + mod-up + NTT banks — ``decompose_banks``) runs
    ONCE, and each rotation reuses those digits through an
    evaluation-domain gather (``ops.galois_digits_banks``; the
    automorphism commutes with per-prime decomposition, so gathering the
    shared digits is bit-identical to decomposing the gathered c1).

    c0/c1: (k, n) u32 NTT-form halves; idx: (R, n) per-rotation gather
    rows; evk_b/evk_a: (k, k+1, R, n) per-rotation stacked Galois key
    digits (the ``_galois_batch_key`` layout).  Returns (k, R, n)
    stacks — rotation r of the input in batch column r.

    Versus R independent ``galois_ks_banks`` dispatches this pays 1
    decomposition instead of R (the dominant cost: 1 iNTT + k*(k+1)
    NTTs each) plus R dyadic inner products and ONE fused mod-down over
    all 2R accumulator halves; the R axis folds into the existing
    (prime, batch_tile) kernel grids, so there is no Python loop over
    rotations or primes anywhere in the path."""
    return _hoisted_rotations_impl(c0, c1, idx, evk_b, evk_a, t, fsp,
                                   use_pallas, tile)


def _hoisted_rotations_impl(c0, c1, idx, evk_b, evk_a, t, fsp=None,
                            use_pallas: bool | None = None,
                            tile: int | None = None):
    k, n = c0.shape
    R = idx.shape[0]
    q = t["qs"][:k][:, None, None]
    kw = dict(use_pallas=use_pallas, tile=tile)
    y = FB.decompose_banks(c1[:, None], t, fsp=fsp, **kw)   # (k, k+1, 1, n)
    # shared-mode gathers: the one decomposition (and the one c0 stack,
    # as a single-"digit" call) fan out to R gather rows in one gather
    yg = ops.galois_digits_banks(y, idx)                    # (k, k+1, R, n)
    acc0 = ops.dyadic_inner_banks(yg, evk_b, t, **kw)       # (k+1, R, n)
    acc1 = ops.dyadic_inner_banks(yg, evk_a, t, **kw)
    # both accumulator halves ride one fused mod-down (batch of 2R)
    acc = jnp.concatenate([acc0, acc1], axis=1)
    ks = mod_down_banks(acc, t, fsp=fsp, **kw)              # (k, 2R, n)
    ks0, ks1 = ks[:, :R], ks[:, R:]
    c0g = ops.galois_digits_banks(c0[None, :, None], idx)[0]
    return addmod(c0g, ks0, q), ks1


@functools.partial(jax.jit, static_argnames=("use_pallas", "tile"),
                   donate_argnums=_DONATE_BANKS)
def galois_ks_many_banks(c0, c1, idx, evk_b, evk_a, t, fsp=None, *,
                         use_pallas: bool | None = None, tile: int | None = None):
    """B slot rotations / conjugations, one program — the batch may MIX
    automorphisms: idx is a (B, n) stack of per-ciphertext gather rows
    and evk_b/evk_a are (k, k+1, B, n) per-ciphertext Galois key digits
    (the batched ``dyadic_inner_banks`` consumes them elementwise).  A
    uniform batch passes the shared (n,) row + (k, k+1, n) digits
    instead — the underlying kernels broadcast either layout.

    c0/c1: (B, k, n) u32 NTT-form halves.  Returns (B, k, n) stacks.
    Both are DONATED off-CPU (fresh ``jnp.stack`` copies at every call
    site, parked via ``retire_donated`` until this program executes —
    see ``multiply_many_banks`` for the policy and the
    pending-destructor hazard); the key/idx/table operands are NOT —
    they live in the plan's caches and must survive the dispatch."""
    return _galois_ks_many_impl(c0, c1, idx, evk_b, evk_a, t, fsp,
                                use_pallas, tile)


def _galois_ks_many_impl(c0, c1, idx, evk_b, evk_a, t, fsp=None,
                         use_pallas: bool | None = None,
                         tile: int | None = None):
    k = c0.shape[1]
    q = t["qs"][:k][None, :, None]
    c0g = ops.galois_banks(c0, idx, batch_leading=True)
    c1g = ops.galois_banks(c1, idx, batch_leading=True)
    ks0, ks1 = batched_keyswitch(c1g.swapaxes(0, 1), evk_b, evk_a, t,
                                 fsp=fsp, use_pallas=use_pallas, tile=tile)
    return addmod(c0g, ks0.swapaxes(0, 1), q), ks1.swapaxes(0, 1)


@functools.partial(jax.jit, static_argnames=("jmap", "imap"))
def plain_mac_banks(b0, b1, diags, qs, mus, *, jmap, imap):
    """Fused BSGS multiply-accumulate stage (the ``fhe.linalg.matvec``
    inner sums): inner_i = sum_j pdiag_{i,j} * rot_j(x), every giant
    group in ONE program.

    b0/b1: (R, k, n) stacked halves of the hoisted baby rotations;
    diags: (D, k, n) stacked plaintext diagonals, sorted by (i, j);
    qs/mus: (k, 1) Barrett columns.  ``jmap[d]`` is diagonal d's row in
    the baby stack and ``imap[d]`` its giant group — both STATIC
    (per-``PtMatrix`` constants), so the accumulation unrolls into a
    pure dyadic MM/MA dataflow with no host round trips: the eager
    per-diagonal ``mul_plain``/``add`` chain this replaces issued ~10
    primitive dispatches per diagonal and dominated serve-path wall
    time (host-bound at ~250 us of dispatch overhead per modmul).
    Returns (G, k, n) inner-sum stacks in giant-group order.  Values
    are bit-identical to the eager chain: modular addition is exact, so
    association order cannot change the result."""
    p0 = mulmod_barrett(diags, b0[jmap, :, :], qs, mus)
    p1 = mulmod_barrett(diags, b1[jmap, :, :], qs, mus)
    outs0, outs1 = [], []
    for g in sorted(set(imap)):
        ds = [d for d, i in enumerate(imap) if i == g]
        acc0, acc1 = p0[ds[0]], p1[ds[0]]
        for d in ds[1:]:
            acc0 = addmod(acc0, p0[d], qs)
            acc1 = addmod(acc1, p1[d], qs)
        outs0.append(acc0)
        outs1.append(acc1)
    return jnp.stack(outs0), jnp.stack(outs1)


# -------------------------------------------- async staging helpers
#
# On the CPU/TPU PJRT runtimes, EAGER ops synchronize: an eager
# ``jnp.stack`` or ``c0[i]`` on a result that is still computing waits
# for it to finish before dispatching.  The batched wrappers below
# stage inputs and split outputs on every call, so doing either eagerly
# re-serializes the whole dispatch chain — the serve engine's
# ping-pong drain would overlap nothing (this was measured: wrapper
# output slicing alone accounted for the full device latency of the
# previous dispatch).  Wrapping the same stack/split/accumulate in
# ``jax.jit`` keeps them on the async dispatch queue: the call returns
# futures immediately and only an explicit ``block_until_ready``
# synchronizes.  They are registered in ``_JITTED_PROGRAMS`` because a
# cold trace of any of them is real XLA work inside a request's latency
# window.

_stack_banks = jax.jit(jnp.stack)


def _stack_ct_banks(arrs):
    """Host-side batch staging with a ``plan.stack`` span: the batched
    scheme ops stack B ciphertext halves into one (B, k, n) device
    array here, and this staging cost is exactly what the async drain's
    ping-pong overlaps — the span makes it visible on the Perfetto
    timeline.  Same jitted ``jnp.stack`` program either way (no new jit
    signature, so the ``fresh_traces`` discipline is untouched)."""
    with obs.span("plan.stack", n=len(arrs)):
        return _stack_banks(arrs)


@functools.partial(jax.jit, static_argnames=("axis",))
def _unstack_banks(x, axis: int = 0):
    return tuple(jnp.moveaxis(x, axis, 0))


@jax.jit
def accumulate_banks(parts0, parts1, qs):
    """Modular sum of L ciphertext halves as one program: parts0/parts1
    are (nonempty) LISTS of (k, n) stacks — passed as a pytree, so no
    eager stacking — and qs the (k, 1) prime columns.  Exact modular
    addition: any association order gives bit-identical sums, so this
    equals the eager left-fold ``add`` chain it replaces (the
    ``fhe.linalg.matvec`` giant-step tail)."""
    acc0, acc1 = parts0[0], parts1[0]
    for p0, p1 in zip(parts0[1:], parts1[1:]):
        acc0 = addmod(acc0, p0, qs)
        acc1 = addmod(acc1, p1, qs)
    return acc0, acc1


# PJRT marks a donated buffer's handle deleted at DISPATCH time, but
# destroying the handle of a donated buffer whose consumer has not yet
# EXECUTED blocks the host until the consumer (and its whole producer
# chain) finishes.  The donated args of ``multiply_many_banks`` /
# ``galois_ks_many_banks`` are throwaway ``_stack_banks`` outputs, so
# letting them die right after the call would synchronize every
# dispatch — the exact serialization the serve engine's ping-pong
# drain exists to avoid (measured: the destructor ate the full device
# latency of the in-flight batch, charged to the call line).  Parking
# them here until the consumer's output reports ready keeps the
# pipeline asynchronous; the deque self-trims on each new retirement,
# so it never holds more than the programs actually in flight.
_RETIRED_DONATIONS: deque = deque()


def retire_donated(probe, *stacks) -> None:
    """Park donated input ``stacks`` until ``probe`` (an output of
    their consumer program) is ready, then let them be collected."""
    _RETIRED_DONATIONS.append((probe, stacks))
    while _RETIRED_DONATIONS:
        head, _ = _RETIRED_DONATIONS[0]
        try:
            if not head.is_ready():
                break
        except Exception:      # probe itself deleted/donated: done
            pass
        _RETIRED_DONATIONS.popleft()


def release_retired() -> None:
    """Drop every parked donation.  Only call once the work has been
    drained (``jax.block_until_ready`` on the outputs) — releasing a
    still-pending donation blocks until its consumer executes."""
    _RETIRED_DONATIONS.clear()


@functools.lru_cache(maxsize=None)
def _scalar_pack(primes: tuple[int, ...]) -> dict:
    return FB.build_scalar_pack(list(primes))


# Every jitted scheme program above, for trace accounting: the programs
# are module-level and shape-keyed, so their jit caches are shared by
# all plans in the process — ``EvalPlan.trace_count`` reads the total
# and callers assert on DELTAS (a serve loop whose warm-up covered its
# traffic must measure delta 0 across a run).
_JITTED_PROGRAMS = (multiply_banks, rescale_banks, galois_ks_banks,
                    multiply_many_banks, rescale_many_banks,
                    hoisted_rotations_banks, galois_ks_many_banks,
                    plain_mac_banks, accumulate_banks,
                    _stack_banks, _unstack_banks)

# The per-mesh ``shard_map`` twins register here as they are built, so
# ``trace_count`` keeps covering every compiled signature in the process
# (a sharded serve loop's ``fresh_traces`` discipline is the same as the
# single-device one).
_SHARDED_PROGRAMS: list = []


_GROUPED_MESH = ("grouped-digit key switching (dnum below the limb count, or "
                 "more than one special prime) has no sharded programs: "
                 "run it on an EvalPlan without a mesh")


@functools.lru_cache(maxsize=None)
def sharded_many_programs(mesh, use_pallas: bool | None = None,
                          tile: int | None = None) -> dict:
    """Jitted ``shard_map`` twins of the batched programs over ``mesh``'s
    "b" axis: the leading ciphertext-batch axis (the hoisted program's
    rotation axis) splits across devices, tables/keys replicate (``P()``
    — the ``NamedSharding``-replicated convention the README documents),
    and each shard runs the IDENTICAL pipeline body on its local rows.
    No collectives anywhere: batch rows never interact, so the gathered
    result is bit-identical to the single-device programs (pinned in
    tests/test_sharded_eval.py).  Callers pad the batch to a multiple of
    the axis size first (``EvalPlan._pad_batch``).

    Five programs: ``multiply`` / ``rescale`` / ``galois_shared`` (one
    gather row + key for the whole batch) / ``galois_mixed``
    (per-ciphertext rows + (k, k+1, B, n) key stacks, both batch-sharded)
    / ``hoisted`` (c0/c1 replicated, the R rotation axis sharded — each
    shard pays its own digit decomposition, trading D-1 extra decomposes
    for a collective-free program).

    Cached per (mesh, use_pallas, tile) — ``Mesh`` is hashable — and
    appended to ``_SHARDED_PROGRAMS`` for ``trace_count``.  Tables of a
    grouped-digit key switch (``up``/``down`` conversion tables) raise
    ``NotImplementedError``: the twins take single-prime digits only."""
    ct = PartitionSpec("b")                    # leading batch axis sharded
    rep = PartitionSpec()                      # replicated tables/keys
    key_b = PartitionSpec(None, None, "b")     # (k, k+1, B, n) key stacks
    col_b = PartitionSpec(None, "b")           # (k, R, n) hoisted outputs
    kw = dict(use_pallas=use_pallas, tile=tile)

    def build(impl, in_specs, out_specs):
        # check_vma=False: the Pallas kernels inside (compiled or
        # interpreted) take replicated tables beside batch-varying
        # blocks, and the bodies are collective-free, so the
        # varying-axes type check has nothing to protect here
        fn = jax.jit(compat.shard_map(functools.partial(impl, **kw),
                                      mesh=mesh, in_specs=in_specs,
                                      out_specs=out_specs, check_vma=False))
        _SHARDED_PROGRAMS.append(fn)

        def call(*args):
            if any(isinstance(a, dict) and ("up" in a or "down" in a)
                   for a in args):
                raise NotImplementedError(_GROUPED_MESH)
            return fn(*args)
        return call

    return {
        "multiply": build(_multiply_many_impl,
                          (ct, ct, ct, ct, rep, rep, rep, rep), (ct, ct)),
        "rescale": build(_rescale_many_impl, (ct, ct, rep, rep), (ct, ct)),
        "galois_shared": build(_galois_ks_many_impl,
                               (ct, ct, rep, rep, rep, rep, rep), (ct, ct)),
        "galois_mixed": build(_galois_ks_many_impl,
                              (ct, ct, ct, key_b, key_b, rep, rep),
                              (ct, ct)),
        "hoisted": build(_hoisted_rotations_impl,
                         (rep, rep, ct, key_b, key_b, rep, rep),
                         (col_b, col_b)),
    }


class EvalPlan:
    """Precomputed device tables + jitted programs for one CkksContext.

    The plan caches per-basis artifacts (packs, stacked keys, gather
    rows) so a serving loop pays keygen/stacking once; ``prepare`` makes
    the warm-up explicit for latency-sensitive callers (see
    examples/private_inference.py).

    ``mesh`` scales the plan out (the paper's replicated-PE tier): an
    axis named "b" routes every batched op through the ``shard_map``
    twins (``sharded_many_programs`` — batch rows split across devices,
    collective-free, bit-identical results); an axis named "k" commits
    the RNS prime axis of the residue stacks to the mesh via
    ``NamedSharding`` and lets XLA's SPMD partitioner shard the plain
    programs, inserting exactly the collectives the decompose/mod-down
    cross-prime reductions need.  A mesh of ONE device is valid and
    exercises the same code path (the tier-1 no-op equivalence test)."""

    def __init__(self, ctx, *, use_pallas: bool | None = None,
                 tile: int | None = None, mesh=None):
        self.ctx = ctx
        self.n = ctx.n
        self.natural = self.n >= ops.FOURSTEP_MIN_N
        self._kw = dict(use_pallas=use_pallas, tile=tile)
        self.mesh = mesh
        self._sharded = None
        self._kmesh = False
        if mesh is not None and (ctx.group > 1 or len(ctx.specials) > 1):
            raise NotImplementedError(_GROUPED_MESH)
        if mesh is not None:
            bad = set(mesh.axis_names) - {"b", "k"}
            if bad:
                raise ValueError(
                    f"EvalPlan: unknown mesh axis name(s) {sorted(bad)} — "
                    "the scale-out convention shards the ciphertext batch "
                    "axis over 'b' and the RNS prime axis over 'k' "
                    "(see README 'Scale-out')")
            if "b" in mesh.axis_names:
                # even a size-1 "b" axis routes through the shard_map
                # twins, so single-device tests cover the sharded path
                self._sharded = sharded_many_programs(mesh, use_pallas, tile)
            self._kmesh = ("k" in mesh.axis_names
                           and int(mesh.shape["k"]) > 1)
        self._keys: dict = {}        # ('relin', basis) | ('galois', g, basis)
        self._batch_keys: dict = {}  # (gs tuple, basis) -> stacked, bounded
        self._idx: dict[int, jnp.ndarray] = {}
        self._ks_tables: dict = {}           # basis -> (t, fsp)
        self._rescale_tables: dict = {}      # basis -> (t, fsp) views
        self.reset_stats()

    # ------------------------------------------------------- mesh helpers

    @property
    def mesh_devices(self) -> int:
        """Shard count of the batch ("b") mesh axis — the serve engine's
        group-sizing multiplier and autotune's ``shards=`` divisor
        (1 when unsharded or k-only)."""
        if self.mesh is not None and "b" in self.mesh.axis_names:
            return int(self.mesh.shape["b"])
        return 1

    def _pad_batch(self, items: list) -> list:
        """Pad a (nonempty) batch list to a multiple of the "b" axis size
        by repeating the last element — ``shard_map`` needs the sharded
        axis divisible by the mesh axis.  Callers zip results against the
        ORIGINAL list, so the pad rows are computed and dropped; counters
        charge only the logical batch."""
        r = (-len(items)) % self.mesh_devices
        return list(items) + [items[-1]] * r

    def _shard_k(self, arr):
        """Commit a residue stack's prime axis (second-to-last: (..., k,
        n)) to the mesh's "k" axis.  Identity when the plan has no
        sharded "k" axis — or when the stack's prime count does not
        divide the axis (``NamedSharding`` requires divisibility and the
        basis shrinks as levels drop, so k-sharding degrades per-basis
        rather than failing); otherwise the jitted programs consume the
        committed operand and XLA SPMD-partitions the whole dispatch."""
        if not self._kmesh or arr.shape[-2] % int(self.mesh.shape["k"]):
            return arr
        spec = PartitionSpec(*([None] * (arr.ndim - 2)), "k", None)
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    # ---------------------------------------------------------- counters
    #
    # Cumulative per-plan dispatch accounting, so callers (the serve
    # engine, the bench gates) can ASSERT how much device work a request
    # pattern paid rather than infer it from wall time:
    #   dispatches   jitted scheme programs launched
    #   key_switches key-switch inner products applied (digit MM/MA +
    #                mod-down passes — the paper's Fig 22 op, the unit
    #                of its 1.63M op/s claim)
    #   decomposes   RNS digit decompositions paid (iNTT + mod-up + NTT
    #                banks).  Hoisting reuse shows up as
    #                key_switches - decomposes > 0: R rotations sharing
    #                one decomposition count R key switches but 1
    #                decompose.

    def reset_stats(self):
        self.stats = {"dispatches": 0, "key_switches": 0, "decomposes": 0}
        return self

    @staticmethod
    def trace_count() -> int:
        """Total compiled signatures across the jitted scheme programs
        (process-wide — the programs are module-level and shared by
        every plan).  Latency-sensitive callers compare deltas: a
        request that pays XLA compilation inside its latency window
        shows up as ``trace_count`` growth, so the serve engine reports
        the per-run delta as ``stats['fresh_traces']`` and a correct
        ``prepare`` warm-up pins it at 0.  Covers the per-mesh
        ``shard_map`` twins too (``_SHARDED_PROGRAMS``)."""
        return sum(getattr(p, "_cache_size", lambda: 0)()
                   for p in _JITTED_PROGRAMS + tuple(_SHARDED_PROGRAMS))

    def _count(self, dispatches=1, key_switches=0, decomposes=0, digits=0):
        self.stats["dispatches"] += dispatches
        self.stats["key_switches"] += key_switches
        self.stats["decomposes"] += decomposes
        if obs.enabled():
            # mirror into the obs metrics registry: the stats dict stays
            # the per-plan source of truth (tests pin its exact values),
            # the registry accumulates process-wide for the snapshot;
            # plan.digits counts the digits mod-upped (decomposes times
            # the digits of the basis) and has no stats entry
            obs.counter_add("plan.dispatches", dispatches)
            obs.counter_add("plan.key_switches", key_switches)
            obs.counter_add("plan.decomposes", decomposes)
            obs.counter_add("plan.digits", digits)

    # ------------------------------------------------------------ tables

    def _packs(self, full: tuple[int, ...]):
        """(t, fsp) for a basis whose *last* prime is the special/dropped
        one.  Past the four-step threshold the size-n twiddles live in
        the FourStepPack and t shrinks to the per-prime scalar columns."""
        if self.natural:
            return _scalar_pack(full), rns.fourstep_basis_pack(full, self.n)
        return rns.basis_pack(full, self.n), None

    def keyswitch_tables(self, basis: tuple[int, ...]):
        """(t, fsp) of a key switch at ``basis``: the packs of basis +
        specials.  Digits of more than one prime add the mod-up's
        conversion tables (``t["up"]``), and more than one special prime
        the mod-down's (``t["down"]``) with P^-1 rows for their product;
        single-prime digits and one special prime add nothing, so their
        programs are the plain centred lift and a one-row mod-down."""
        if basis not in self._ks_tables:
            ctx = self.ctx
            full = basis + ctx.specials
            t, fsp = self._packs(full)
            groups = ctx.digit_groups(basis)
            if max(map(len, groups)) > 1:
                if basis != ctx.qs[:len(basis)]:
                    raise ValueError(f"basis {basis} is not a prefix of the "
                                     "chain: its digits are not consecutive")
                t = dict(t, up=FB.base_conv_tables(groups, full))
            if len(ctx.specials) > 1:
                t = dict(t, **FB.pinv_pack(full, len(ctx.specials)),
                         down=FB.base_conv_tables([ctx.specials], basis))
            self._ks_tables[basis] = (t, fsp)
        return self._ks_tables[basis]

    def _ks_span(self, basis) -> dict:
        """Span args of a key-switching program at ``basis``: its digits
        and special primes."""
        return {"digits": len(self.ctx.digit_groups(basis)),
                "alpha": len(self.ctx.specials)}

    def rescale_tables(self, basis: tuple[int, ...]):
        if basis not in self._rescale_tables:
            if self.natural:
                # the FourStepPack carries no basis-relative rows, so
                # rescale shares a slice of the keyswitch pack
                # (basis+special) instead of building a second full pack
                # per basis; only the cheap scalar columns (pinv =
                # q_l^-1) are rescale-specific
                _, ks_fsp = self.keyswitch_tables(basis)
                self._rescale_tables[basis] = (
                    _scalar_pack(basis),
                    FB.slice_fourstep_pack(ks_fsp, slice(0, len(basis))))
            else:
                self._rescale_tables[basis] = self._packs(basis)
        return self._rescale_tables[basis]

    # -------------------------------------------------------------- keys

    def _stacked(self, key, builder):
        if key not in self._keys:
            evk = builder()
            self._keys[key] = (jnp.stack([p[0].data for p in evk]),
                               jnp.stack([p[1].data for p in evk]))
        return self._keys[key]

    def relin_key(self, basis: tuple[int, ...]):
        """(digits, k+alpha, n) stacked relinearization key tensors."""
        return self._stacked(("relin", basis),
                             lambda: self.ctx.relin_keys(basis))

    def galois_key(self, g: int, basis: tuple[int, ...]):
        return self._stacked(("galois", g, basis),
                             lambda: self.ctx.galois_keys(g, basis))

    # The stacked mixed-batch tensors are big ((k, k+1, B, n) x2 per
    # pattern) and the pattern space is order-sensitive (the serve
    # engine canonicalizes by sorting each galois group by g), so this
    # cache is a BOUNDED LRU: steady-state traffic that re-dispatches
    # the same g sequences stays resident, adversarially random traffic
    # evicts instead of growing device memory forever.
    _BATCH_KEY_CACHE_MAX = 32

    def _galois_batch_key(self, gs: tuple[int, ...], basis: tuple[int, ...]):
        """(k, k+1, B, n) per-ciphertext key stacks + (B, n) gather rows
        for a mixed-automorphism batch, cached per (gs, basis) — a
        steady-state serving pattern re-dispatches the same g sequence,
        and restacking B full key tensors per call is pure waste.

        Each lookup is a ``plan.batch_key`` span (``hit``, ``b``; on a
        miss ``resident_bytes``, the cache's summed ``nbytes`` after the
        insert, from the arrays' shapes), read by the benchmark's
        ``plan.batch_key_miss_pct``; a miss's span times the restack."""
        key = (gs, basis)
        hit = key in self._batch_keys
        with obs.span("plan.batch_key", hit=hit, b=len(gs)) as sp:
            if hit:
                # LRU touch: steady-state patterns stay resident
                self._batch_keys[key] = self._batch_keys.pop(key)
            else:
                if len(self._batch_keys) >= self._BATCH_KEY_CACHE_MAX:
                    self._batch_keys.pop(next(iter(self._batch_keys)))
                keys = [self.galois_key(g, basis) for g in gs]
                self._batch_keys[key] = (
                    jnp.stack([kb for kb, _ in keys], axis=2),  # (k, k+1, B, n)
                    jnp.stack([ka for _, ka in keys], axis=2),
                    jnp.stack([self.eval_idx(g) for g in gs]))
                if obs.enabled():
                    sp.annotate(resident_bytes=sum(
                        x.nbytes for entry in self._batch_keys.values()
                        for x in entry))
        return self._batch_keys[key]

    def eval_idx(self, g: int) -> jnp.ndarray:
        """(n,) NTT-domain gather row for sigma_g under this ring's
        frequency-order convention (natural past the four-step threshold,
        bitrev below it)."""
        if g not in self._idx:
            self._idx[g] = jnp.asarray(
                galois_eval_perm(g, self.n, self.natural), jnp.int32)
        return self._idx[g]

    def rotation_group_element(self, r: int) -> int:
        return pow(5, r, 2 * self.n)

    def prepare(self, basis: tuple[int, ...] | None = None,
                rotations=(), conjugate: bool = False, relin: bool = True,
                warm_jit: bool = True, batch_sizes=(), hoisted_sets=(),
                matvecs=()):
        """Eagerly build every table/key/gather-row a serving loop will
        need, so no request pays keygen or pack construction.

        ``warm_jit`` additionally traces and compiles each jitted scheme
        program with a zero ciphertext, so the first real request is a
        pure device dispatch (the programs are shape-keyed: one warm-up
        covers every rotation amount at the same basis).  A serving
        engine should pass its padded batch signatures as
        ``batch_sizes`` (e.g. the multiples of its batch tile it expects
        to see): the ``*_many`` programs are shape-keyed on B, and an
        unwarmed batch size pays full XLA compilation on the first real
        request group.  ``hoisted_sets`` likewise warms
        ``hoisted_rotations_banks`` (shape-keyed on R) per rotation-amount
        tuple — e.g. a BSGS matvec's baby-step set (``fhe.linalg``
        reports it as ``PtMatrix.baby_set``).

        ``matvecs`` takes ``fhe.linalg.PtMatrix`` packs and warms the
        WHOLE matvec composite each one runs — the hoisted baby-step
        dispatch at the pack's ``baby_set`` AND the mixed-amount
        giant-step ``rotate_many`` at B = len(giant_set), at the pack's
        own basis.  Neither signature is implied by ``batch_sizes``
        (matvec giant batches are not tile-padded) or ``hoisted_sets``
        alone, so without this a post-prepare matvec pays XLA
        compilation inside its latency window; a warmed plan pins
        ``trace_count`` across the request (tests/test_linalg.py).

        One prepare covers ONE basis; serve loops admitting traffic at
        several levels call prepare once per serving basis.

        The dispatch counters (``stats``) are reset on exit, so warm-up
        traffic never pollutes a caller's accounting."""
        basis = tuple(basis if basis is not None else self.ctx.qs)
        self.keyswitch_tables(basis)
        self.rescale_tables(basis)
        if relin:
            self.relin_key(basis)
        gs = [g for g in (self.rotation_group_element(r) for r in rotations)
              if g != 1]
        if conjugate:
            gs.append(2 * self.n - 1)
        hoist_gs = {self.rotation_group_element(r)
                    for rset in hoisted_sets for r in rset} - {1}
        for g in gs + sorted(hoist_gs - set(gs)):
            self.galois_key(g, basis)
            self.eval_idx(g)
        if warm_jit:
            z = RnsPoly(jnp.zeros((len(basis), self.n), jnp.uint32), basis, True)
            zct = Ciphertext(z, z, 1.0)
            if relin:
                self.multiply(zct, zct)
            if len(basis) > 1:
                self.rescale(zct)
            if gs:
                self.apply_galois(zct, gs[0])
            for B in batch_sizes:
                cts = [zct] * B
                if relin:
                    self.multiply_many(cts, cts)
                if len(basis) > 1:
                    self.rescale_many(cts)
                if gs:                       # uniform batch (shared key)...
                    self.galois_ks_many(cts, [gs[0]] * B)
                if len(set(gs)) > 1 and B > 1:  # ...and the mixed signature
                    mix = [gs[i % len(gs)] for i in range(B)]
                    self.galois_ks_many(cts, mix)
            for rset in hoisted_sets:
                self.rotate_hoisted(zct, list(rset))
        for M in matvecs:
            mv_basis = tuple(M.basis)
            self.keyswitch_tables(mv_basis)
            for r in set(M.baby_set) | set(M.giant_set):
                g = self.rotation_group_element(r)
                if g != 1:
                    self.galois_key(g, mv_basis)
                    self.eval_idx(g)
            if warm_jit:
                # run the full composite on a zero ciphertext: compiles
                # the R-keyed hoisted baby dispatch AND the giant-step
                # rotate_many signature (mixed or uniform, exactly as
                # matvec will issue it) — the import is deferred because
                # linalg imports this module
                from repro.fhe import linalg as _linalg
                z = RnsPoly(jnp.zeros((len(mv_basis), self.n), jnp.uint32),
                            mv_basis, True)
                _linalg.matvec(self, M, Ciphertext(z, z, 1.0))
        return self.reset_stats()

    # ------------------------------------------------------- scheme ops

    def multiply(self, a: Ciphertext, b: Ciphertext) -> Ciphertext:
        check_same_basis("multiply", a, b)
        check_level("multiply", a)
        basis = a.primes
        t, fsp = self.keyswitch_tables(basis)
        eb, ea = self.relin_key(basis)
        ks = self._ks_span(basis)
        with obs.span("plan.program", program="multiply", **ks):
            c0, c1 = multiply_banks(self._shard_k(a.c0.data),
                                    self._shard_k(a.c1.data),
                                    self._shard_k(b.c0.data),
                                    self._shard_k(b.c1.data),
                                    eb, ea, t, fsp, **self._kw)
        self._count(1, key_switches=1, decomposes=1, digits=ks["digits"])
        return Ciphertext(RnsPoly(c0, basis, True), RnsPoly(c1, basis, True),
                          a.scale * b.scale)

    def rescale(self, a: Ciphertext) -> Ciphertext:
        check_level("rescale", a, need=1)
        basis = a.primes
        t, fsp = self.rescale_tables(basis)
        with obs.span("plan.program", program="rescale"):
            c0, c1 = rescale_banks(self._shard_k(a.c0.data),
                                   self._shard_k(a.c1.data), t, fsp,
                                   **self._kw)
        self._count(1)
        rest = basis[:-1]
        return Ciphertext(RnsPoly(c0, rest, True), RnsPoly(c1, rest, True),
                          a.scale / basis[-1])

    def apply_galois(self, a: Ciphertext, g: int) -> Ciphertext:
        check_level("apply_galois", a)
        basis = a.primes
        t, fsp = self.keyswitch_tables(basis)
        eb, ea = self.galois_key(g, basis)
        ks = self._ks_span(basis)
        with obs.span("plan.program", program="galois_ks", **ks):
            c0, c1 = galois_ks_banks(self._shard_k(a.c0.data),
                                     self._shard_k(a.c1.data),
                                     self.eval_idx(g),
                                     eb, ea, t, fsp, **self._kw)
        self._count(1, key_switches=1, decomposes=1, digits=ks["digits"])
        return Ciphertext(RnsPoly(c0, basis, True), RnsPoly(c1, basis, True),
                          a.scale)

    def rotate(self, a: Ciphertext, r: int) -> Ciphertext:
        g = self.rotation_group_element(r)
        if g == 1:                       # identity automorphism: no-op
            return Ciphertext(a.c0, a.c1, a.scale)   # fresh ct, no aliasing
        return self.apply_galois(a, g)

    def conjugate(self, a: Ciphertext) -> Ciphertext:
        return self.apply_galois(a, 2 * self.n - 1)

    # --------------------------------------------- batched scheme ops
    #
    # B independent ciphertexts at ONE basis -> one jitted dispatch.
    # Mixed bases raise (batching never crosses levels — group by basis
    # upstream; ``fhe.serve.CkksServeEngine`` does exactly that).

    def _common_basis(self, op: str, cts) -> tuple[int, ...]:
        basis = cts[0].primes
        for ct in cts[1:]:
            if ct.primes != basis:
                raise ValueError(
                    f"{op}: batch mixes bases — {sorted({c.primes for c in cts}, key=len)}; "
                    "batched dispatch requires every ciphertext at the "
                    "same basis (group by level first)")
        return basis

    def multiply_many(self, As, Bs) -> list[Ciphertext]:
        """B tensor+relinearize products as one ``multiply_many_banks``
        dispatch.  As/Bs: equal-length ciphertext lists, all at one
        basis; pairwise scales may differ (tracked per result)."""
        if len(As) != len(Bs):
            raise ValueError(f"multiply_many: {len(As)} lhs vs {len(Bs)} rhs")
        if not As:
            return []
        for a, b in zip(As, Bs):
            check_same_basis("multiply_many", a, b)
            check_level("multiply_many", a)
        basis = self._common_basis("multiply_many", list(As) + list(Bs))
        t, fsp = self.keyswitch_tables(basis)
        eb, ea = self.relin_key(basis)
        stack = lambda ps: _stack_ct_banks([p.data for p in ps])
        ks = self._ks_span(basis)
        with obs.span("plan.program", program="multiply_many", n=len(As),
                      sharded=self._sharded is not None, **ks):
            if self._sharded is not None:
                Ap, Bp = self._pad_batch(list(As)), self._pad_batch(list(Bs))
                c0, c1 = self._sharded["multiply"](
                    stack([a.c0 for a in Ap]), stack([a.c1 for a in Ap]),
                    stack([b.c0 for b in Bp]), stack([b.c1 for b in Bp]),
                    eb, ea, t, fsp)
            else:
                a0s, a1s = stack([a.c0 for a in As]), stack([a.c1 for a in As])
                c0, c1 = multiply_many_banks(
                    self._shard_k(a0s), self._shard_k(a1s),
                    self._shard_k(stack([b.c0 for b in Bs])),
                    self._shard_k(stack([b.c1 for b in Bs])),
                    eb, ea, t, fsp, **self._kw)
                retire_donated(c0, a0s, a1s)
        self._count(1, key_switches=len(As), decomposes=len(As),
                    digits=len(As) * ks["digits"])
        return [Ciphertext(RnsPoly(r0, basis, True),
                           RnsPoly(r1, basis, True), a.scale * b.scale)
                for r0, r1, a, b in zip(_unstack_banks(c0),
                                        _unstack_banks(c1), As, Bs)]

    def rescale_many(self, cts) -> list[Ciphertext]:
        """Rescale B ciphertexts (one basis) as one fused mod-down over
        all 2B halves."""
        if not cts:
            return []
        for ct in cts:
            check_level("rescale_many", ct, need=1)
        basis = self._common_basis("rescale_many", cts)
        t, fsp = self.rescale_tables(basis)
        with obs.span("plan.program", program="rescale_many", n=len(cts),
                      sharded=self._sharded is not None):
            if self._sharded is not None:
                pad = self._pad_batch(list(cts))
                c0, c1 = self._sharded["rescale"](
                    _stack_ct_banks([ct.c0.data for ct in pad]),
                    _stack_ct_banks([ct.c1.data for ct in pad]), t, fsp)
            else:
                c0, c1 = rescale_many_banks(
                    self._shard_k(_stack_ct_banks([ct.c0.data for ct in cts])),
                    self._shard_k(_stack_ct_banks([ct.c1.data for ct in cts])),
                    t, fsp, **self._kw)
        self._count(1)
        rest = basis[:-1]
        return [Ciphertext(RnsPoly(r0, rest, True),
                           RnsPoly(r1, rest, True), ct.scale / basis[-1])
                for r0, r1, ct in zip(_unstack_banks(c0),
                                      _unstack_banks(c1), cts)]

    def galois_ks_many(self, cts, gs) -> list[Ciphertext]:
        """B automorphisms (one basis, possibly MIXED group elements gs)
        as one ``galois_ks_many_banks`` dispatch: per-ciphertext gather
        rows + per-ciphertext stacked Galois key digits.  A uniform
        batch (every g equal — conjugate_many, same-amount rotation
        groups) keeps the SHARED (k, k+1, n) key and (n,) gather row
        instead of replicating them B times; both layouts flow through
        the same program (the kernels broadcast the 3-D evk / 1-D idx)."""
        if len(cts) != len(gs):
            raise ValueError(f"galois_ks_many: {len(cts)} cts vs {len(gs)} gs")
        if not cts:
            return []
        for ct in cts:
            check_level("galois_ks_many", ct)
        basis = self._common_basis("galois_ks_many", cts)
        t, fsp = self.keyswitch_tables(basis)
        ks = self._ks_span(basis)
        with obs.span("plan.program", program="galois_ks_many", n=len(cts),
                      sharded=self._sharded is not None, **ks):
            if self._sharded is not None:
                pad_cts = self._pad_batch(list(cts))
                pad_gs = self._pad_batch(list(gs))
                s0 = _stack_ct_banks([ct.c0.data for ct in pad_cts])
                s1 = _stack_ct_banks([ct.c1.data for ct in pad_cts])
                if len(set(pad_gs)) == 1:
                    eb, ea = self.galois_key(pad_gs[0], basis)
                    c0, c1 = self._sharded["galois_shared"](
                        s0, s1, self.eval_idx(pad_gs[0]), eb, ea, t, fsp)
                else:
                    eb, ea, idx = self._galois_batch_key(tuple(pad_gs), basis)
                    c0, c1 = self._sharded["galois_mixed"](
                        s0, s1, idx, eb, ea, t, fsp)
            else:
                if len(set(gs)) == 1:
                    eb, ea = self.galois_key(gs[0], basis)
                    idx = self.eval_idx(gs[0])
                else:
                    eb, ea, idx = self._galois_batch_key(tuple(gs), basis)
                s0 = self._shard_k(_stack_ct_banks([ct.c0.data for ct in cts]))
                s1 = self._shard_k(_stack_ct_banks([ct.c1.data for ct in cts]))
                c0, c1 = galois_ks_many_banks(s0, s1, idx, eb, ea, t, fsp,
                                              **self._kw)
                retire_donated(c0, s0, s1)
        self._count(1, key_switches=len(cts), decomposes=len(cts),
                    digits=len(cts) * ks["digits"])
        return [Ciphertext(RnsPoly(r0, basis, True),
                           RnsPoly(r1, basis, True), ct.scale)
                for r0, r1, ct in zip(_unstack_banks(c0),
                                      _unstack_banks(c1), cts)]

    # ----------------------------------------------- hoisted rotations
    #
    # R rotations of ONE ciphertext -> one dispatch paying ONE digit
    # decomposition (decompose-once convention: decompose_banks runs on
    # c1 as received, and every rotation gathers those shared digits in
    # the evaluation domain).  This is the primitive slot linear algebra
    # (``fhe.linalg`` BSGS matvec baby steps) runs on.

    def hoisted_galois(self, a: Ciphertext, gs) -> list[Ciphertext]:
        """Apply R automorphisms (group elements ``gs``, need not be
        distinct) to ``a`` as ONE ``hoisted_rotations_banks`` dispatch.
        Bit-identical to ``[self.apply_galois(a, g) for g in gs]`` —
        pinned in tests/test_linalg.py."""
        gs = tuple(gs)
        if not gs:
            return []
        check_level("hoisted_galois", a)
        basis = a.primes
        t, fsp = self.keyswitch_tables(basis)
        ks = self._ks_span(basis)
        with obs.span("plan.program", program="hoisted_galois", n=len(gs),
                      sharded=self._sharded is not None, **ks):
            if self._sharded is not None:
                # shard the rotation axis: pad gs to the mesh width and
                # drop the pad columns on unpack (each shard re-runs the
                # shared decomposition locally — collective-free)
                pad_gs = tuple(self._pad_batch(list(gs)))
                eb, ea, idx = self._galois_batch_key(pad_gs, basis)
                c0, c1 = self._sharded["hoisted"](a.c0.data, a.c1.data, idx,
                                                  eb, ea, t, fsp)
            else:
                eb, ea, idx = self._galois_batch_key(gs, basis)
                c0, c1 = hoisted_rotations_banks(self._shard_k(a.c0.data),
                                                 self._shard_k(a.c1.data), idx,
                                                 eb, ea, t, fsp, **self._kw)
        self._count(1, key_switches=len(gs), decomposes=1,
                    digits=ks["digits"])
        return [Ciphertext(RnsPoly(r0, basis, True),
                           RnsPoly(r1, basis, True), a.scale)
                for r0, r1 in zip(_unstack_banks(c0, axis=1)[:len(gs)],
                                  _unstack_banks(c1, axis=1)[:len(gs)])]

    def rotate_hoisted(self, a: Ciphertext, rs) -> list[Ciphertext]:
        """Rotate one ciphertext by every amount in ``rs`` with the
        key-switch decomposition hoisted: one dispatch, one decompose,
        len(rs) key switches.  Identity amounts (r = 0 mod slots) are
        answered host-side exactly like ``rotate``."""
        gs = [self.rotation_group_element(r) for r in rs]
        live = [i for i, g in enumerate(gs) if g != 1]
        out = [Ciphertext(a.c0, a.c1, a.scale) for _ in gs]
        if live:
            rotated = self.hoisted_galois(a, tuple(gs[i] for i in live))
            for i, ct in zip(live, rotated):
                out[i] = ct
        return out

    def rotate_many(self, cts, rs) -> list[Ciphertext]:
        """Rotate B ciphertexts by per-ciphertext amounts ``rs`` in one
        dispatch (identity rotations are returned as-is, exactly like
        ``rotate``; the rest batch through ``galois_ks_many``)."""
        if len(cts) != len(rs):
            raise ValueError(f"rotate_many: {len(cts)} cts vs {len(rs)} rs")
        gs = [self.rotation_group_element(r) for r in rs]
        live = [i for i, g in enumerate(gs) if g != 1]
        out = [Ciphertext(ct.c0, ct.c1, ct.scale) for ct in cts]
        if live:
            rotated = self.galois_ks_many([cts[i] for i in live],
                                          [gs[i] for i in live])
            for i, ct in zip(live, rotated):
                out[i] = ct
        return out

    def conjugate_many(self, cts) -> list[Ciphertext]:
        return self.galois_ks_many(cts, [2 * self.n - 1] * len(cts))
