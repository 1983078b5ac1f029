"""Public jit'd entry points for the NTT/dyadic compute layer.

Dispatch policy: Pallas kernels target TPU; on other backends the
kernels run in interpret mode for validation, but the *default* hot path
there is the pure-jnp reference (same math, faster under XLA:CPU).
``use_pallas=True`` forces the kernel path (tests do this).  The Galois
automorphism is one XLA gather on every backend (``galois_banks``):
Mosaic has no lane gather past 128 lanes.

Ring element dtypes: CKKS rows are u32; a small ring (ML-KEM's u16
``RingSpec``) may pass u16 or u32 rows.  The kernels always compute in
u32 lanes with the ring's arithmetic width (``bits``, read from the
pack's dtype); outputs come back in the input's dtype.

Two entry-point families:

* Single-prime (``ntt``/``intt``/``dyadic_mul``/``dyadic_mac``), taking
  an ``NTTParams`` for one modulus.
* Multi-prime banks (``ntt_banks``/``intt_banks``/``dyadic_inner_banks``),
  taking a TablePack dict (see ``fhe.batched``) whose per-prime rows are
  stacked on axis 0 — the paper's Fig 22 parallel NTT-bank array.  The
  vmap reference path is the non-TPU default, mirroring the single-prime
  policy.

Ciphertext-batch axis convention: every banks entry point also accepts
``batch_leading=True``, meaning the input is a ``(b, k, ..., n)`` stack
of ``b`` independent ciphertext polynomials over the same k-prime basis.
The leading axis is folded into the existing (prime, batch_tile) kernel
grid — one dispatch transforms all ``b*k`` residue rows — and the output
keeps the leading layout.  This is the layout the batched EvalPlan
programs (``fhe.evalplan.multiply_many_banks`` etc.) and the serving
engine (``fhe.serve``) ride on.

Pallas interpret-mode resolution lives in ONE place:
``kernels.resolve_interpret`` (the kernel wrappers' default when no
explicit flag is passed), so no call site here needs to thread
``interpret=...`` and none can silently leave the interpreter on for a
TPU backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.modmath import dtype_bits
from repro.core.params import NTTParams, bitrev_perm
from repro.kernels import autotune, ntt_kernel, dyadic_kernel, ref

# Single-kernel tile budget: below this ring size the whole log2(n)-stage
# transform runs as ONE fused banks kernel; at or above it the large-N
# four-step pipeline (``ntt_fourstep_banks``) takes over — two batched
# banks passes + the fused twiddle-correction kernel (paper §IX, and the
# ROADMAP "every FHE workload with N >= 2^13" north star).
FOURSTEP_MIN_N = 1 << 13


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_batch(x, tile):
    b = x.shape[0]
    pad = (-b) % tile
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    return x, b


def ntt(x, p: NTTParams, *, negacyclic: bool = True, use_pallas: bool | None = None,
        tile: int | None = None, lazy: bool = True):
    """Batched forward NTT.  x: (..., n) u32 -> (..., n) u32 (bitrev order).

    ``tile=None`` resolves through ``kernels.autotune`` (explicit arg >
    env pin > cache > default), always clamped to the batch — a 1-row
    input dispatches a 1-row grid, not an 8x zero-padded one.  ``lazy``
    selects the deferred-reduction butterflies; the epilogue fully
    reduces either way, so outputs are bit-identical."""
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    x = jnp.asarray(x)
    if not use_pallas:
        return ref.ntt_fwd_ref(x, p, negacyclic, lazy=lazy)
    shape = x.shape
    x2 = x.reshape(-1, p.n)
    tile = autotune.resolve_tile("ntt", 1, p.n, x2.shape[0], tile)
    x2, b = _pad_batch(x2, tile)
    out = ntt_kernel.ntt_fwd_pallas(
        x2, jnp.asarray(p.tw), jnp.asarray(p.twp),
        jnp.asarray(p.psi_pows)[None, :], jnp.asarray(p.psi_pows_p)[None, :],
        q=p.q, stages=p.stages, negacyclic=negacyclic, tile=tile, lazy=lazy)
    return out[:b].reshape(shape)


def intt(x, p: NTTParams, *, negacyclic: bool = True, use_pallas: bool | None = None,
         tile: int | None = None, lazy: bool = True):
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    x = jnp.asarray(x)
    if not use_pallas:
        return ref.ntt_inv_ref(x, p, negacyclic, lazy=lazy)
    shape = x.shape
    x2 = x.reshape(-1, p.n)
    tile = autotune.resolve_tile("intt", 1, p.n, x2.shape[0], tile)
    x2, b = _pad_batch(x2, tile)
    out = ntt_kernel.ntt_inv_pallas(
        x2, jnp.asarray(p.itw), jnp.asarray(p.itwp),
        jnp.asarray(p.ipsi_ninv)[None, :], jnp.asarray(p.ipsi_ninv_p)[None, :],
        q=p.q, stages=p.stages, negacyclic=negacyclic,
        ninv=p.ninv, ninv_p=p.ninv_p, tile=tile, lazy=lazy)
    return out[:b].reshape(shape)


def dyadic_mul(a, b, p: NTTParams, *, use_pallas: bool | None = None,
               tile: int | None = None, lazy: bool = True):
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    if not use_pallas:
        return ref.dyadic_mul_ref(a, b, p.q, p.barrett_mu, lazy=lazy)
    a = jnp.asarray(a)
    shape = a.shape
    a2 = a.reshape(-1, p.n)
    b2 = jnp.asarray(b).reshape(-1, p.n)
    tile = autotune.resolve_tile("dyadic_mul", 1, p.n, a2.shape[0], tile)
    a2, nb = _pad_batch(a2, tile)
    b2, _ = _pad_batch(b2, tile)
    out = dyadic_kernel.dyadic_mul(a2, b2, q=p.q, mu=p.barrett_mu, tile=tile,
                                   lazy=lazy)
    return out[:nb].reshape(shape)


def dyadic_mac(acc, a, b, p: NTTParams, *, use_pallas: bool | None = None,
               tile: int | None = None, lazy: bool = True):
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    if not use_pallas:
        return ref.dyadic_mac_ref(acc, a, b, p.q, p.barrett_mu, lazy=lazy)
    acc = jnp.asarray(acc)
    shape = acc.shape
    nb = acc.reshape(-1, p.n).shape[0]
    tile = autotune.resolve_tile("dyadic_mac", 1, p.n, nb, tile)
    f = lambda t: _pad_batch(jnp.asarray(t).reshape(-1, p.n), tile)[0]
    out = dyadic_kernel.dyadic_mac(f(acc), f(a), f(b), q=p.q, mu=p.barrett_mu,
                                   tile=tile, lazy=lazy)
    return out[:nb].reshape(shape)


# ------------------------------------------------ multi-prime NTT banks

def _pad_mid(x3, tile):
    """Pad the batch (middle) axis of (k, b, n) to a tile multiple."""
    b = x3.shape[1]
    pad = (-b) % tile
    if pad:
        z = jnp.zeros((x3.shape[0], pad, x3.shape[2]), x3.dtype)
        x3 = jnp.concatenate([x3, z], axis=1)
    return x3, b


def _ring_bits(t: dict) -> int:
    """Arithmetic width of a pack's ring (32 for CKKS RNS primes, 16 for
    small rings), read from its static table dtype."""
    return dtype_bits(t["qs"].dtype)


def _rows(t: dict, k: int, *names):
    """First-k prime rows of the named TablePack entries (so a pack for
    a superset basis, e.g. basis+special, works on k-row inputs)."""
    return tuple(t[name][:k] for name in names)


def _swap_ct_axis(x):
    """(b, k, ..., n) ciphertext-batch stack -> (k, b, ..., n) prime-major
    layout (and back — it's its own inverse).  The moved axis lands in
    the middle dims every banks entry point already folds into the
    (prime, batch_tile) kernel grid."""
    return jnp.swapaxes(jnp.asarray(x), 0, 1)


def _spanned(fn):
    """Wrap a banks entry point in an ``obs.span("ops.<name>")``.  When
    the call happens inside a jit trace (the EvalPlan programs), the
    span records trace-time host work, not device compute — still the
    right thing to see on the timeline, since retracing inside a
    latency window IS the cost being hunted.  Disabled, the wrapper is
    one flag check.  The benchmark's ``mlkem.host_us_per_req`` subtracts
    these spans from their ``mlkem.*`` parents."""
    name = f"ops.{fn.__name__}"

    @functools.wraps(fn)
    def wrapper(*args, **kw):
        if not obs.enabled():
            return fn(*args, **kw)
        with obs.span(name, cat="kernel"):
            return fn(*args, **kw)
    return wrapper


def _ct_batch_axis(fn):
    """Give a banks entry point the ciphertext-batch convention in one
    place: ``batch_leading=True`` reads the first argument as a
    (b, k, ..., n) stack — b independent polynomials over the same
    basis — swaps the ciphertext axis behind the prime axis, re-enters
    the prime-major path (which folds it into the (prime, batch_tile)
    grid), and swaps the output back."""
    @functools.wraps(fn)
    def wrapper(x, *args, batch_leading: bool = False, **kw):
        if batch_leading:
            return _swap_ct_axis(fn(_swap_ct_axis(x), *args, **kw))
        return fn(x, *args, **kw)
    return wrapper


@_spanned
@_ct_batch_axis
def ntt_banks(x, t: dict, *, negacyclic: bool = True,
              use_pallas: bool | None = None, tile: int | None = None,
              lazy: bool = True, reduce_out: bool = True):
    """Batched multi-prime forward NTT.  x: (k, ..., n) u32, row i
    reduced mod t['qs'][i]; t: TablePack for (at least) those k primes.
    One fused kernel gridded over (prime, batch_tile) on the Pallas
    path; a vmap over prime rows on the reference path.

    ``batch_leading=True`` flips the convention to a (b, k, ..., n)
    ciphertext-batch stack: b independent polynomials over the same
    basis, folded into the one kernel grid (see module docstring).

    ``lazy`` defers the butterfly reductions ([0, 2q) between stages);
    the default ``reduce_out=True`` epilogue makes the output canonical
    and bit-identical to the eager path.  ``reduce_out=False`` (lazy
    only) hands the raw [0, 2q) representatives to a lazy-aware consumer
    — the four-step pipeline's twiddle pass absorbs that reduction."""
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    x = jnp.asarray(x)
    k, n = x.shape[0], x.shape[-1]
    qs, tw, twp, psi, psip = _rows(t, k, "qs", "tw", "twp", "psi", "psip")
    if not use_pallas:
        return ref.ntt_fwd_banks_ref(x.astype(qs.dtype), qs, tw, twp, psi,
                                     psip, negacyclic, lazy=lazy,
                                     reduce_out=reduce_out).astype(x.dtype)
    shape = x.shape
    x3 = x.reshape(k, -1, n)
    tile = autotune.resolve_tile("ntt_banks", k, n, x3.shape[1], tile,
                                 dtype=qs.dtype.name)
    x3, b = _pad_mid(x3, tile)
    stages = tw.shape[1]
    out = ntt_kernel.ntt_fwd_banks_pallas(
        x3, qs, tw, twp, psi, psip, stages=stages, negacyclic=negacyclic,
        tile=tile, lazy=lazy, reduce_out=reduce_out, bits=_ring_bits(t))
    if stages != n.bit_length() - 1:
        out = out[..., ntt_kernel.incomplete_perm(stages, n)]
    return out[:, :b].reshape(shape).astype(x.dtype)


@_spanned
@_ct_batch_axis
def intt_banks(x, t: dict, *, negacyclic: bool = True,
               use_pallas: bool | None = None, tile: int | None = None,
               lazy: bool = True, reduce_out: bool = True):
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    x = jnp.asarray(x)
    k, n = x.shape[0], x.shape[-1]
    qs, ninv, ninv_p, itw, itwp, ipsin, ipsinp = _rows(
        t, k, "qs", "ninv", "ninv_p", "itw", "itwp", "ipsin", "ipsinp")
    if not use_pallas:
        return ref.ntt_inv_banks_ref(x.astype(qs.dtype), qs, ninv, ninv_p,
                                     itw, itwp, ipsin, ipsinp, negacyclic,
                                     lazy=lazy, reduce_out=reduce_out
                                     ).astype(x.dtype)
    shape = x.shape
    x3 = x.reshape(k, -1, n)
    tile = autotune.resolve_tile("intt_banks", k, n, x3.shape[1], tile,
                                 dtype=qs.dtype.name)
    x3, b = _pad_mid(x3, tile)
    stages = itw.shape[1]
    if stages != n.bit_length() - 1:
        x3 = x3[..., np.argsort(ntt_kernel.incomplete_perm(stages, n))]
    out = ntt_kernel.ntt_inv_banks_pallas(
        x3, qs, ninv, ninv_p, itw, itwp, ipsin, ipsinp, stages=stages,
        negacyclic=negacyclic, tile=tile, lazy=lazy, reduce_out=reduce_out,
        bits=_ring_bits(t))
    return out[:, :b].reshape(shape).astype(x.dtype)


@_spanned
@_ct_batch_axis
def twiddle_mul_banks(x, w, wp, qs, *, use_pallas: bool | None = None,
                      tile: int | None = None, lazy: bool = False):
    """Fused per-prime weight-row multiply: x (k, ..., n) u32, w/wp (k, n)
    weight rows + Shoup companions, qs (k,).  This is the four-step step-3
    twiddle correction (and the negacyclic psi pre/post-weights) as one
    (prime, batch_tile) kernel on the Pallas path.

    Accepts any u32 input representatives (the Shoup product reduces them
    exactly); ``lazy=True`` emits the [0, 2q) representative for a
    lazy-aware consumer instead of the canonical value."""
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    x = jnp.asarray(x)
    if not use_pallas:
        return ref.twiddle_mul_banks_ref(x, qs, w, wp, lazy=lazy)
    k, n = x.shape[0], x.shape[-1]
    shape = x.shape
    x3 = x.reshape(k, -1, n)
    tile = autotune.resolve_tile("twiddle_mul_banks", k, n, x3.shape[1], tile,
                                 dtype=x.dtype.name)
    x3, b = _pad_mid(x3, tile)
    out = ntt_kernel.twiddle_mul_banks_pallas(x3, qs, w, wp,
                                              tile=tile, lazy=lazy)
    return out[:, :b].reshape(shape)


@_spanned
@_ct_batch_axis
def galois_banks(x, idx):
    """Galois automorphism in the NTT domain: out[..., j] = x[..., idx[j]].

    x: (k, ..., n) u32 NTT-form residue rows; idx: (n,) int32 slot
    permutation from ``core.params.galois_eval_perm`` (the same row for
    every prime — root-exponent arithmetic never touches q).  One XLA
    gather on every backend (Mosaic has no lane gather past 128 lanes);
    this replaces the host iNTT -> permute -> NTT round trip for
    rotate/conjugate.

    A (B, n) ``idx`` applies gather row j to batch row j (B must equal
    the product of x's middle dims), so one dispatch can mix rotation
    amounts across a ciphertext batch; ``batch_leading=True`` reads x as
    a (b, k, ..., n) ciphertext-batch stack as in ``ntt_banks``."""
    x = jnp.asarray(x)
    idx = jnp.asarray(idx, jnp.int32)
    if idx.ndim == 2:
        k, n = x.shape[0], x.shape[-1]
        assert idx.shape[0] == int(np.prod(x.shape[1:-1], dtype=np.int64)), \
            (idx.shape, x.shape)
        return ref.galois_banks_ref(x.reshape(k, -1, n), idx).reshape(x.shape)
    return ref.galois_banks_ref(x, idx)


@_spanned
def galois_digits_banks(ext, idx):
    """Galois gather over key-switch digit extensions — the hoisted-
    rotation move: apply per-batch gather rows to a SHARED digit
    decomposition instead of re-decomposing per rotation.

    ext: (d, k, B, n) u32 NTT-domain digit extensions (the
    ``fhe.batched.decompose_banks`` layout — the R rotation amounts of a
    hoisted batch fold into the B axis); idx: (B, n) int32 gather rows,
    row b applied to batch column b of EVERY digit and prime row (the
    automorphism permutation never depends on the digit or the modulus).
    Returns (d, k, B, n), as one XLA gather.

    A (d, k, 1, n) ext against a (B, n) idx with B > 1 runs in SHARED
    mode — the hoisted decompose-once layout: every gather row reads
    the one shared digit stack (out[d, p, b, j] = ext[d, p, 0,
    idx[b, j]]), which is never replicated B-fold in HBM."""
    ext = jnp.asarray(ext)
    idx = jnp.asarray(idx, jnp.int32)
    d, k, b, n = ext.shape
    bi = idx.shape[0]
    assert idx.shape == (bi, n) and (b == 1 or bi == b), \
        (idx.shape, ext.shape)
    return ref.galois_digits_banks_ref(ext, idx)


# ------------------------------------------- large-N four-step pipeline

@functools.lru_cache(maxsize=None)
def _brev(n: int) -> np.ndarray:
    """Bit-reversal permutation — an involution, so the same gather
    converts bitrev->natural and natural->bitrev."""
    return bitrev_perm(n)


def fourstep_dims(fp: dict) -> tuple[int, int]:
    """(n1, n2) of a four-step pack, read from static table shapes (the
    pack holds no Python ints so it can ride through jit as a pytree)."""
    return fp["pack1"]["tw"].shape[-1] * 2, fp["pack2"]["tw"].shape[-1] * 2


@_spanned
@_ct_batch_axis
def ntt_fourstep_banks(x, fp: dict, *, negacyclic: bool = True,
                       use_pallas: bool | None = None, tile: int | None = None,
                       lazy: bool = True):
    """Large-N forward NTT via the four-step (Bailey) decomposition with
    every pass on the banks kernels — the paper's §IX schedule (two
    passes of batched NTT-N1/NTT-N2 units with a reorder in between).

    x: (k, ..., n) u32 with row i reduced mod fp["qs"][i] (or a
    (b, k, ..., n) ciphertext-batch stack with ``batch_leading=True``);
    fp: a FourStepPack from ``fhe.batched.build_fourstep_pack`` for at
    least those k primes (extra rows are ignored, like ``ntt_banks``).

    Pipeline:  [psi pre-weight] -> column NTT-N1 bank pass (batch folds
    the N2 columns) -> fused w^(j2*k1) twiddle kernel -> row NTT-N2 bank
    pass -> transpose readout.  Output is in *natural* frequency order
    (A_hat[k2*n1 + k1]), unlike the bitrev order of the single-kernel
    path; ``intt_fourstep_banks`` consumes the same convention, so any
    NTT-domain data stays internally consistent per ring size.

    In lazy mode the inter-pass values ride in [0, 2q): the psi
    pre-weight and pass 1 emit unreduced representatives, the step-3
    Shoup twiddle absorbs them exactly (it accepts any u32), and pass 2's
    epilogue restores [0, q) — the output is bit-identical to eager."""
    x = jnp.asarray(x)
    k = x.shape[0]
    n1, n2 = fourstep_dims(fp)
    n = n1 * n2
    assert x.shape[-1] == n, (x.shape, n1, n2)
    kw = dict(use_pallas=use_pallas, tile=tile, lazy=lazy)
    qs = fp["qs"][:k]
    shape = x.shape
    x = x.reshape(k, -1, n)
    b = x.shape[1]
    if negacyclic:
        x = twiddle_mul_banks(x, fp["psi"][:k], fp["psip"][:k], qs, **kw)
    # pass 1: column NTT-N1 units; the N2 columns fold into the kernel
    # batch so all k*b*n2 transforms run in one (prime, tile) grid
    xt = x.reshape(k, b, n1, n2).swapaxes(-1, -2).reshape(k, b * n2, n1)
    xt = ntt_banks(xt, fp["pack1"], negacyclic=False, reduce_out=False,
                   **kw)[..., _brev(n1)]
    x = xt.reshape(k, b, n2, n1).swapaxes(-1, -2).reshape(k, b, n)
    # step 3: fused twiddle correction (the inter-pass reorder weights)
    x = twiddle_mul_banks(x, fp["tw"][:k], fp["twp"][:k], qs, **kw)
    # pass 2: row NTT-N2 units (epilogue restores the canonical band)
    xr = x.reshape(k, b * n1, n2)
    xr = ntt_banks(xr, fp["pack2"], negacyclic=False,
                   **kw)[..., _brev(n2)]
    # readout: A_hat[k2*n1 + k1] = D[k1, k2]
    return xr.reshape(k, b, n1, n2).swapaxes(-1, -2).reshape(shape)


@_spanned
@_ct_batch_axis
def intt_fourstep_banks(x, fp: dict, *, negacyclic: bool = True,
                        use_pallas: bool | None = None, tile: int | None = None,
                        lazy: bool = True):
    """Inverse of ``ntt_fourstep_banks`` (natural-order input).  The two
    sub-iNTT bank passes each contribute 1/Ni, so no separate n^-1; the
    negacyclic psi^-i post-weight is the plain inverse-psi row.  Lazy
    handoff mirrors the forward pipeline: unreduced between passes, the
    final multiply (psi^-i, or pass 1's ninv epilogue) fully reduces."""
    x = jnp.asarray(x)
    k = x.shape[0]
    n1, n2 = fourstep_dims(fp)
    n = n1 * n2
    assert x.shape[-1] == n, (x.shape, n1, n2)
    kw = dict(use_pallas=use_pallas, tile=tile, lazy=lazy)
    qs = fp["qs"][:k]
    shape = x.shape
    x = x.reshape(k, -1, n)
    b = x.shape[1]
    # undo the readout: D[k1, k2] from A_hat[k2*n1 + k1]
    x = x.reshape(k, b, n2, n1).swapaxes(-1, -2)            # (k, b, n1, n2)
    # inverse pass 2: row iNTT-N2 banks (bitrev input order)
    xr = x.reshape(k, b * n1, n2)[..., _brev(n2)]
    xr = intt_banks(xr, fp["pack2"], negacyclic=False, reduce_out=False, **kw)
    # undo the twiddle correction
    x = twiddle_mul_banks(xr.reshape(k, b, n), fp["itw"][:k], fp["itwp"][:k],
                          qs, **kw)
    # inverse pass 1: column iNTT-N1 banks; when a psi post-weight
    # follows it absorbs the reduction, else the ninv epilogue reduces
    xt = (x.reshape(k, b, n1, n2).swapaxes(-1, -2)
          .reshape(k, b * n2, n1)[..., _brev(n1)])
    xt = intt_banks(xt, fp["pack1"], negacyclic=False,
                    reduce_out=not negacyclic, **kw)
    x = xt.reshape(k, b, n2, n1).swapaxes(-1, -2).reshape(k, b, n)
    if negacyclic:
        x = twiddle_mul_banks(x, fp["ipsi"][:k], fp["ipsip"][:k], qs,
                              use_pallas=use_pallas, tile=tile)  # full reduce
    return x.reshape(shape)


@_spanned
def dyadic_inner_banks(ext, evk, t: dict, *, use_pallas: bool | None = None,
                       tile: int | None = None, lazy: bool = True):
    """Fused key-switch inner product: out[j] = sum_i ext[i, j] .* evk[i, j]
    mod q_j.  ext: (d, k, B, n) NTT-domain digit extensions — a
    ciphertext batch folds into the B axis; evk: (d, k, n) key digits
    shared by the whole batch, or (d, k, B, n) per-batch-element digits
    (a Galois batch mixing rotation keys); t: TablePack whose rows align
    with axis 1."""
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    ext = jnp.asarray(ext)
    evk = jnp.asarray(evk)
    assert ext.ndim == 4 and evk.ndim in (3, 4) \
        and ext.shape[1] == t["qs"].shape[0]
    if evk.ndim == 4:
        assert evk.shape == ext.shape, (evk.shape, ext.shape)
    if not use_pallas:
        return ref.dyadic_inner_banks_ref(ext, evk, t["qs"], t["mu"], lazy=lazy)
    d, k, b, n = ext.shape
    tile = autotune.resolve_tile("dyadic_inner_banks", k, n, b, tile,
                                 dtype=ext.dtype.name)
    pad = (-b) % tile
    if pad:
        z = jnp.zeros((d, k, pad, n), ext.dtype)
        ext = jnp.concatenate([ext, z], axis=2)
        if evk.ndim == 4:
            evk = jnp.concatenate([evk, z], axis=2)
    out = dyadic_kernel.dyadic_inner_banks(
        ext, evk, t["qs"], t["mu"], digits=d, tile=tile, lazy=lazy)
    return out[:, :b]


@_spanned
def dyadic_basemul_banks(a, b, t: dict, *, batch_leading: bool = False,
                         use_pallas: bool | None = None,
                         tile: int | None = None, lazy: bool = True):
    """Degree-1 basecase multiplication of an INCOMPLETE ring (a
    ``core.ringspec.RingSpec`` with block=2, e.g. ML-KEM): pair j of the
    CG-ordered NTT domain is (x[j], x[j+n/2]) and

        c0[j] = a0·b0 + γ_j·(a1·b1)      c1[j] = a0·b1 + a1·b0

    with the per-pair ζ factors γ from the ring pack's ``gamma`` /
    ``gammap`` rows.  a, b: (k, ..., n) canonical [0, q) NTT-domain
    operands over the pack's rings (or (b, k, ..., n) stacks with
    ``batch_leading=True`` — both operands swap); t: a
    ``core.ringspec.ring_table_pack``.  This is the incomplete-domain
    counterpart of the complete transform's pointwise product."""
    if batch_leading:
        return _swap_ct_axis(
            dyadic_basemul_banks(_swap_ct_axis(a), _swap_ct_axis(b), t,
                                 use_pallas=use_pallas, tile=tile, lazy=lazy))
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    assert a.shape == b.shape, (a.shape, b.shape)
    k, n = a.shape[0], a.shape[-1]
    qs, mus, gamma, gammap = _rows(t, k, "qs", "mu", "gamma", "gammap")
    if not use_pallas:
        rd = qs.dtype
        return ref.dyadic_basemul_banks_ref(a.astype(rd), b.astype(rd), qs,
                                            mus, gamma, gammap, lazy=lazy
                                            ).astype(a.dtype)
    shape = a.shape
    a3 = a.reshape(k, -1, n)
    b3 = b.reshape(k, -1, n)
    tile = autotune.resolve_tile("dyadic_basemul_banks", k, n, a3.shape[1],
                                 tile, dtype=qs.dtype.name)
    a3, bsz = _pad_mid(a3, tile)
    b3, _ = _pad_mid(b3, tile)
    out = dyadic_kernel.dyadic_basemul_banks(
        a3, b3, qs, mus, gamma, gammap, tile=tile, lazy=lazy,
        bits=_ring_bits(t))
    return out[:, :bsz].reshape(shape).astype(a.dtype)
