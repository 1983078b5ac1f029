"""Fully-jittable batched FHE kernels for AOT dry-runs and benchmarks.

Unlike fhe.ckks (host-orchestrated, exact), these functions take all
NTT/twiddle/key tables as explicit array arguments so they can be
lowered with ShapeDtypeStructs on the production mesh — the sce-ntt
dry-run cells (paper §IX workloads at scale).

Table pack layout for a basis of ``k`` primes over ring n:
  qs      (k,)  u32      prime moduli
  tw/twp  (k, s, n/2)    forward CG twiddles + Shoup companions
  itw/itwp(k, s, n/2)    inverse
  ninv/ninv_p (k,)       n^-1 per prime
  psi/psip, ipsin/ipsinp (k, n)  negacyclic weights (ipsin folds n^-1)
  mu      (k,)  u32      Barrett constants (dyadic ct x ct products)

FourStepPack layout (``build_fourstep_pack``) for large N = N1*N2 — the
factor tables the §IX four-step banks pipeline consumes
(``kernels.ops.ntt_fourstep_banks``):
  qs        (k,)  u32    prime moduli
  pack1     TablePack dict for the N1 column transform, whose psi is
                         the big transform's psi^N2 (so omega1 = w^N2)
  pack2     TablePack dict for the N2 row transform (psi^N1)
  tw/twp    (k, n)       step-3 twiddle correction w^(j2*k1), flattened
                         [k1*N2 + j2] to match the inter-pass layout
  itw/itwp  (k, n)       its inverse
  psi/psip  (k, n)       negacyclic psi^i pre-weights (natural order)
  ipsi/ipsip(k, n)       psi^-i post-weights (NO n^-1 fold: the two
                         sub-iNTT passes already contribute 1/N1 * 1/N2)
and the tables of the one-kernel transform (``ntt_kernel.fourstep_*``),
each with its Shoup companion (suffix ``p``):
  pre       (k, n1, n2)      psi^i, rows in bit-reversed order
  post      (k, n1, n2)      psi^-i * n^-1, rows in bit-reversed order
  fwd_a     (k, n1 + 16, n2) ``row_twiddles`` of the n1-point root,
  inv_a                      and of its inverse, broadcast over lanes
  fwd_c     (k, n2 + 16, n1) the same for the n2-point root
  inv_c
  ninv/ninv_p (k,)           n^-1 (the cyclic inverse)
The multi-pass entries feed the reference pipeline
(``ref.ntt_fourstep_banks_ref``), the one-kernel entries the kernel.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.modmath import (addmod, submod, mulmod_shoup, mulmod_barrett,
                                shoup_precompute, barrett_precompute)
from repro.core.ntt import cg_ntt, cg_intt
from repro.core.params import bitrev_perm, make_ntt_params
from repro.kernels import base_conv_kernel, ops
from repro.kernels.ntt_kernel import row_twiddles


@dataclasses.dataclass
class TablePack:
    qs: jnp.ndarray
    tw: jnp.ndarray
    twp: jnp.ndarray
    itw: jnp.ndarray
    itwp: jnp.ndarray
    ninv: jnp.ndarray
    ninv_p: jnp.ndarray
    psi: jnp.ndarray
    psip: jnp.ndarray
    ipsin: jnp.ndarray
    ipsinp: jnp.ndarray
    mu: jnp.ndarray

    def tree(self):
        return dataclasses.asdict(self)


def table_pack_shapes(k: int, n: int):
    s = n.bit_length() - 1
    u = jnp.uint32
    sds = jax.ShapeDtypeStruct
    return {
        "qs": sds((k,), u), "tw": sds((k, s, n // 2), u), "twp": sds((k, s, n // 2), u),
        "itw": sds((k, s, n // 2), u), "itwp": sds((k, s, n // 2), u),
        "ninv": sds((k,), u), "ninv_p": sds((k,), u),
        "psi": sds((k, n), u), "psip": sds((k, n), u),
        "ipsin": sds((k, n), u), "ipsinp": sds((k, n), u),
        "mu": sds((k,), u),
        # P^-1 mod q_j (last prime treated as special P), Shoup companions
        "pinv": sds((max(k - 1, 1),), u), "pinv_p": sds((max(k - 1, 1),), u),
    }


def build_table_pack(primes: list[int], n: int) -> dict:
    return pack_from_ntt_params([make_ntt_params(n, q=q) for q in primes])


def pack_from_ntt_params(params: list) -> dict:
    """Stack per-prime ``NTTParams`` rows into the TablePack layout.  The
    pinv rows treat the last prime as the special P (key-switch mod-down);
    for packs that are not key-switch bases they simply ride along."""
    rows = {k: [] for k in table_pack_shapes(1, 1)}
    primes = [p.q for p in params]
    for p in params:
        q = p.q
        rows["qs"].append(np.uint32(q))
        rows["tw"].append(p.tw)
        rows["twp"].append(p.twp)
        rows["itw"].append(p.itw)
        rows["itwp"].append(p.itwp)
        rows["ninv"].append(np.uint32(p.ninv))
        rows["ninv_p"].append(np.uint32(p.ninv_p))
        rows["psi"].append(p.psi_pows)
        rows["psip"].append(p.psi_pows_p)
        rows["ipsin"].append(p.ipsi_ninv)
        rows["ipsinp"].append(p.ipsi_ninv_p)
        rows["mu"].append(np.uint32(barrett_precompute(q)))
    pinv, pinv_p = _pinv_rows(primes)
    rows["pinv"], rows["pinv_p"] = list(pinv), list(pinv_p)
    return {k: jnp.asarray(np.stack(v)) for k, v in rows.items()}


def _pinv_rows(primes, drop: int = 1) -> tuple[np.ndarray, np.ndarray]:
    """P^-1 mod q_j rows + Shoup companions, P the product of the last
    ``drop`` primes (the special primes, or the one prime rescale drops)
    — the mod-down convention, shared by every pack builder."""
    P = math.prod(primes[-drop:])
    src = primes[:-drop] if len(primes) > drop else primes
    pinv = np.array([pow(P, -1, q) if P % q else 1 for q in src],
                    dtype=np.uint32)
    pinv_p = np.array([shoup_precompute(int(v), q)
                       for v, q in zip(pinv, src)], dtype=np.uint32)
    return pinv, pinv_p


def pinv_pack(primes, drop: int) -> dict:
    """The ``pinv``/``pinv_p`` entries of a pack whose last ``drop``
    primes are divided out by the mod-down."""
    pinv, pinv_p = _pinv_rows(list(primes), drop)
    return {"pinv": jnp.asarray(pinv), "pinv_p": jnp.asarray(pinv_p)}


def base_conv_tables(groups, dst) -> dict:
    """Constants of the centred fast base conversion from each prime
    group of ``groups`` to the primes ``dst`` (``ops.base_conv_banks``):
    for group j of product M and its prime m_i, the Shoup pairs of
    (M/m_i)^-1 mod m_i and of M/m_i mod p for each p of dst, and the
    offset 2p - m_i that lifts a negative centred term into [0, 2p).  A
    group shorter than the longest is padded with zero weights (its
    padded source rows are zero, so they add nothing)."""
    m, t = max(len(g) for g in groups), len(dst)
    z = lambda *shape: np.zeros(shape, dtype=np.uint32)
    src, inv, inv_p = z(len(groups), m), z(len(groups), m), z(len(groups), m)
    f, f_p, off = (z(len(groups), m, t) for _ in range(3))
    for j, grp in enumerate(groups):
        M = math.prod(grp)
        src[j] = grp[0]
        for i, mi in enumerate(grp):
            Mi = M // mi
            src[j, i] = mi
            inv[j, i] = pow(Mi % mi, -1, mi)
            inv_p[j, i] = shoup_precompute(int(inv[j, i]), mi)
            for c, p in enumerate(dst):
                f[j, i, c] = Mi % p
                f_p[j, i, c] = shoup_precompute(int(f[j, i, c]), p)
                off[j, i, c] = 2 * p - mi
    return {k: jnp.asarray(v) for k, v in dict(
        src=src, inv=inv, inv_p=inv_p, dst=np.array(dst, dtype=np.uint32),
        f=f, f_p=f_p, off=off).items()}


def build_scalar_pack(primes: list[int]) -> dict:
    """Just the per-prime scalar rows of a TablePack (qs/mu/pinv/pinv_p).
    ``batched_keyswitch(fsp=...)`` never touches the size-n twiddle
    tables of ``t`` — the four-step pack carries its own — so large-N
    callers can pass this instead of paying a full ``build_table_pack``
    (which costs O(n log n) host modexps per prime)."""
    qs = np.array(primes, dtype=np.uint32)
    mu = np.array([barrett_precompute(q) for q in primes], dtype=np.uint32)
    pinv, pinv_p = _pinv_rows(primes)
    return {k: jnp.asarray(v) for k, v in
            {"qs": qs, "mu": mu, "pinv": pinv, "pinv_p": pinv_p}.items()}


def fourstep_pack_from_params(fsps: list) -> dict:
    """Stack per-prime ``core.fourstep.FourStepParams`` into the
    FourStepPack layout (see module docstring)."""
    def flat(name):
        return jnp.asarray(np.stack(
            [np.asarray(getattr(f, name)).reshape(-1) for f in fsps]))

    pack = {
        "qs": jnp.asarray(np.array([f.q for f in fsps], dtype=np.uint32)),
        "pack1": pack_from_ntt_params([f.p1 for f in fsps]),
        "pack2": pack_from_ntt_params([f.p2 for f in fsps]),
        "tw": flat("tw_mat"), "twp": flat("tw_mat_p"),
        "itw": flat("itw_mat"), "itwp": flat("itw_mat_p"),
        "psi": flat("psi_mat"), "psip": flat("psi_mat_p"),
        "ipsi": flat("ipsi_mat"), "ipsip": flat("ipsi_mat_p"),
    }
    rows = {name: [] for name in _KERNEL_TABLES}
    for f in fsps:
        for name, v in _kernel_tables(f).items():
            rows[name].append(v)
    pack.update({name: jnp.asarray(np.stack(v)) for name, v in rows.items()})
    return pack


_KERNEL_TABLES = ("pre", "prep", "post", "postp", "fwd_a", "fwd_ap",
                  "inv_a", "inv_ap", "fwd_c", "fwd_cp", "inv_c", "inv_cp",
                  "ninv", "ninv_p")


def _shoup_np(w: np.ndarray, q: int) -> np.ndarray:
    return ((w.astype(np.uint64) << np.uint64(32)) // np.uint64(q)
            ).astype(np.uint32)


def _kernel_tables(f) -> dict:
    """One prime's tables of the one-kernel four-step transform (see the
    module docstring), from its ``core.fourstep.FourStepParams``."""
    q, n1, n2 = f.q, f.n1, f.n2
    brev = bitrev_perm(n1)
    ninv = pow(n1 * n2, -1, q)
    post = (f.ipsi_mat.astype(np.uint64) * ninv % q).astype(np.uint32)[brev]

    def lanes(root, r, width):
        col = row_twiddles(root, q, r)
        return np.broadcast_to(col[:, None], (col.size, width))

    out = {"pre": f.psi_mat[brev], "post": post,
           "fwd_a": lanes(f.p1.omega, n1, n2),
           "inv_a": lanes(pow(f.p1.omega, -1, q), n1, n2),
           "fwd_c": lanes(f.p2.omega, n2, n1),
           "inv_c": lanes(pow(f.p2.omega, -1, q), n2, n1)}
    out.update({f"{name}p": _shoup_np(v, q) for name, v in list(out.items())})
    out["ninv"] = np.uint32(ninv)
    out["ninv_p"] = np.uint32(shoup_precompute(ninv, q))
    return out


def build_fourstep_pack(primes: list[int], n: int, n1: int | None = None,
                        n2: int | None = None) -> dict:
    """FourStepPack for a prime basis over ring n = n1*n2 (defaults to the
    balanced ``params.fourstep_split``).  Building this costs two small
    ``make_ntt_params`` per prime plus O(n) host twiddle tables — far
    cheaper than a full size-n parameter build."""
    from repro.core.fourstep import make_fourstep_params
    from repro.core.params import fourstep_split
    if n1 is None or n2 is None:
        n1, n2 = fourstep_split(n)
    assert n1 * n2 == n
    return fourstep_pack_from_params(
        [make_fourstep_params(n1, n2, q) for q in primes])


def slice_fourstep_pack(fp: dict, rows) -> dict:
    """View of a FourStepPack restricted to prime rows ``rows``."""
    flat = ("qs", "tw", "twp", "itw", "itwp", "psi", "psip", "ipsi",
            "ipsip") + _KERNEL_TABLES
    return {"pack1": slice_pack(fp["pack1"], rows),
            "pack2": slice_pack(fp["pack2"], rows),
            **{k: fp[k][rows] for k in flat}}


# ------------------------------------------------ per-prime primitives

def ntt_fwd_i(x, t: dict, i):
    """Negacyclic fwd NTT of x (..., n) under prime row i (traced index).
    Fully unrolled stages -> XLA fuses butterfly chains (§Perf it. 1)."""
    q = t["qs"][i]
    x = mulmod_shoup(x, t["psi"][i], t["psip"][i], q)
    s = t["tw"].shape[1]
    return cg_ntt(x, t["tw"][i], t["twp"][i], q, unroll=2)


def ntt_inv_i(x, t: dict, i):
    q = t["qs"][i]
    s = t["itw"].shape[1]
    x = cg_intt(x, t["itw"][i], t["itwp"][i], 0, 0, q, apply_ninv=False, unroll=2)
    return mulmod_shoup(x, t["ipsin"][i], t["ipsinp"][i], q)


def extend_centered(coeffs, src_q, dst_qs):
    """EXACT single-prime base conversion (alpha=1 mod-up), jit form.
    coeffs: (..., n) u32 mod src_q -> (k, ..., n) u32 mod each dst prime."""
    c = coeffs.astype(jnp.int32)
    half = (src_q // jnp.uint32(2)).astype(jnp.int32)
    c = jnp.where(c > half, c - src_q.astype(jnp.int32), c)

    def per(qd):
        qd = qd.astype(jnp.int32)
        r = c % qd
        return jnp.where(r < 0, r + qd, r).astype(jnp.uint32)
    return jax.vmap(per)(dst_qs)


# ---------------------------------------------------------- keyswitch

def slice_pack(t: dict, rows) -> dict:
    """View of a TablePack restricted to prime rows ``rows`` (a slice).
    The pinv rows (P^-1 mod q_j) and a key-switch pack's base-conversion
    tables (``up``, ``down``) are basis-relative and left intact."""
    basis_relative = ("pinv", "pinv_p", "up", "down")
    return {k: (v if k in basis_relative else v[rows]) for k, v in t.items()}


def _fwd_banks(x, pack, fpk, kw):
    return (ops.ntt_fourstep_banks(x, fpk, **kw) if fpk is not None
            else ops.ntt_banks(x, pack, **kw))


def _inv_banks(x, pack, fpk, kw):
    return (ops.intt_fourstep_banks(x, fpk, **kw) if fpk is not None
            else ops.intt_banks(x, pack, **kw))


def mod_down_banks(acc, t: dict, *, fsp: dict | None = None,
                   use_pallas: bool | None = None, tile: int | None = None,
                   lazy: bool = True):
    """RNS floor by the *last* primes of ``t``'s basis, fully batched —
    the paper's Fig 22 stage 4 (INTT + base-ext + NTT + MS) as one fused
    device program.

    acc: (k+alpha, B, n) u32 NTT form over t's k+alpha primes; returns
    (k, B, n) over the first k.  alpha is 1, or the number of special
    primes where ``t`` carries their conversion tables (``down``, see
    ``base_conv_tables``).  The last rows run one banks iNTT, the centred
    conversion carries them over the basis (the plain centred lift of one
    prime, ``extend_centered``, or ``ops.base_conv_banks`` from alpha
    primes), one banks NTT returns it to evaluation form, and the
    subtract + per-prime scalar multiply by P^-1 (the precomputed
    ``pinv`` columns) finish the rounded division.  This single routine
    serves both the key-switch mod-down by P (``batched_keyswitch``) and
    ciphertext rescale by q_l (``evalplan.rescale_banks``) — pass a pack
    whose basis ends with the primes being dropped.  ``fsp`` routes every
    transform through the large-N four-step pipeline, exactly as in
    ``batched_keyswitch``."""
    alpha = t["down"]["inv"].shape[1] if "down" in t else 1
    k = acc.shape[0] - alpha
    kw = dict(use_pallas=use_pallas, tile=tile, lazy=lazy)
    top = slice(k, k + alpha)
    fs_last = slice_fourstep_pack(fsp, top) if fsp is not None else None
    lastc = _inv_banks(acc[k:], slice_pack(t, top), fs_last, kw)
    if "down" in t:
        ext = ops.base_conv_banks(lastc[None], t["down"],
                                  name=base_conv_kernel.MODDOWN,
                                  use_pallas=use_pallas, tile=tile)[:, 0]
    else:
        ext = extend_centered(lastc[0], t["qs"][k], t["qs"][:k])
    extn = _fwd_banks(ext, slice_pack(t, slice(0, k)), fsp, kw)
    qcol = t["qs"][:k, None, None]
    d = submod(acc[:k], extn, qcol)
    return mulmod_shoup(d, t["pinv"][:, None, None], t["pinv_p"][:, None, None],
                        qcol)


def decompose_banks(d2, t: dict, *, fsp: dict | None = None,
                    use_pallas: bool | None = None, tile: int | None = None,
                    lazy: bool = True):
    """RNS digit decomposition + mod-up, fully batched — the front half
    of the paper's Fig 22 pipeline (INTT units -> base extension -> NTT
    banks), extracted so callers can pay it ONCE and reuse the digits.

    d2: (k, B, n) u32, NTT form over the k-prime basis; t: TablePack for
    k+alpha primes (rows k.. the special primes); fsp as in
    ``batched_keyswitch``.  Returns (d, k+alpha, B, n): NTT-domain digit
    extensions (digit axis first), ready for ``ops.dyadic_inner_banks``.

    Digits: one per prime (the plain centred lift), or, where ``t``
    carries the mod-up tables (``up``, see ``base_conv_tables``), groups
    of m consecutive basis primes (the last one possibly shorter), each
    extended by the centred fast base conversion ``ops.base_conv_banks``.

    This is the hoisting primitive: a Galois automorphism commutes with
    the digit decomposition (sigma_g permutes integer coefficients with
    sign flips, which survives the centered lift, the centred
    conversion, whose terms are odd functions of their residues, and
    every modular reduction), so R rotations of one ciphertext can share
    a single decomposition — gather these digits R ways in the
    evaluation domain instead of decomposing R times
    (``evalplan.hoisted_rotations_banks``).

    Every stage is one multi-prime dispatch: the digit INTTs run as k
    bank rows, the mod-up is one vmap or one conversion call over all
    digits, and all d*(k+alpha) forward NTTs run as one (prime, batch)
    grid with the digit axis folded into the batch.  No Python loop over
    primes or digits."""
    k, B, n = d2.shape
    kw = dict(use_pallas=use_pallas, tile=tile, lazy=lazy)
    tb = slice_pack(t, slice(0, k))

    ci = _inv_banks(d2, tb, fsp, kw)                          # INTT units
    if "up" in t:                                             # mod-up:
        d, m = t["up"]["inv"].shape                           # (k+a, d, B, n)
        x = jnp.pad(ci, ((0, d * m - k), (0, 0), (0, 0))).reshape(d, m, B, n)
        ext = ops.base_conv_banks(x, t["up"], name=base_conv_kernel.MODUP,
                                  use_pallas=use_pallas, tile=tile)
    else:
        ext = jax.vmap(lambda c, q: extend_centered(c, q, t["qs"])
                       )(ci, t["qs"][:k]).transpose(1, 0, 2, 3)  # (k+1, k, ..)
    # NTT banks: fold the digit axis into the batch so all d*(k+alpha)
    # transforms run in ONE (prime, batch_tile) grid.
    y = _fwd_banks(ext, t, fsp, kw)                           # (k+a, d, B, n)
    return y.transpose(1, 0, 2, 3)                            # (digit, prime, B, n)


def batched_keyswitch(d2, evk_b, evk_a, t: dict, *, fsp: dict | None = None,
                      use_pallas: bool | None = None, tile: int | None = None,
                      lazy: bool = True):
    """Paper Fig 22 pipeline, vectorized over a ciphertext batch AND the
    RNS prime rows — the bank-parallel production path.

    d2:      (k, B, n) u32, NTT form over the k-prime basis (digit rows);
             a ciphertext batch folds into the B axis (the batched
             EvalPlan programs dispatch B independent ciphertexts here)
    evk_b/a: (d, k+alpha, n) key-switch key digits over basis+specials,
             shared by the whole batch — or (d, k+alpha, B, n) per-batch
             digits, for a Galois batch mixing rotation keys
    t:       TablePack for k+alpha primes (rows k.. the special primes,
             with the ``up``/``down`` conversion tables of grouped
             digits and alpha > 1; see ``decompose_banks``)
    fsp:     optional FourStepPack for the same k+alpha primes — when given,
             every NTT/iNTT stage dispatches through the large-N
             four-step banks pipeline (``ops.ntt_fourstep_banks``)
             instead of the single fused kernel.  Required for rings
             past the single-kernel tile budget (n >= ops.FOURSTEP_MIN_N);
             d2 and the evk digits must then hold natural-order NTT rows
             (the four-step convention), and ``t`` may be the cheap
             ``build_scalar_pack`` (its twiddle tables go unused).
    Returns (ks0, ks1): (k, B, n) over the original basis.

    The front half (digit INTTs + mod-up + forward NTTs) lives in
    ``decompose_banks``; the whole digit inner product is then one fused
    dyadic-MAC call per output polynomial.  There is no Python-level
    per-prime loop left in this hot path.
    """
    kw = dict(use_pallas=use_pallas, tile=tile, lazy=lazy)
    y = decompose_banks(d2, t, fsp=fsp, **kw)                 # (digit, prime, B, n)
    acc0 = ops.dyadic_inner_banks(y, evk_b, t, **kw)          # MM/MA arrays
    acc1 = ops.dyadic_inner_banks(y, evk_a, t, **kw)

    md = functools.partial(mod_down_banks, t=t, fsp=fsp,      # RNS floor + MS
                           use_pallas=use_pallas, tile=tile, lazy=lazy)
    return md(acc0), md(acc1)
