"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the *same functions* the rest of the framework uses on CPU;
kernel tests sweep shapes/dtypes and assert exact equality (integer
arithmetic — no tolerance needed)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import ntt as _ntt
from repro.core.modmath import (
    addmod,
    lazy_addmod,
    mulmod_barrett,
    mulmod_barrett_lazy,
    mulmod_shoup,
    mulmod_shoup_lazy,
)
from repro.core.params import NTTParams, bitrev_perm


def ntt_fwd_ref(x, p: NTTParams, negacyclic: bool, lazy: bool = False):
    x = jnp.asarray(x)
    if negacyclic:
        return _ntt.ntt_negacyclic(x, p, lazy=lazy)
    return _ntt.ntt_cyclic(x, p, lazy=lazy)


def ntt_inv_ref(x, p: NTTParams, negacyclic: bool, lazy: bool = False):
    x = jnp.asarray(x)
    if negacyclic:
        return _ntt.intt_negacyclic(x, p, lazy=lazy)
    return _ntt.intt_cyclic(x, p, lazy=lazy)


def dyadic_mul_ref(a, b, q: int, mu: int, lazy: bool = False):
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    qc = jnp.uint32(q)
    if lazy:
        r = mulmod_barrett_lazy(a, b, qc, jnp.uint32(mu))
        return jnp.where(r >= qc, r - qc, r)
    return mulmod_barrett(a, b, qc, jnp.uint32(mu))


def dyadic_mac_ref(acc, a, b, q: int, mu: int, lazy: bool = False):
    qc = jnp.uint32(q)
    if lazy:
        p = mulmod_barrett_lazy(jnp.asarray(a), jnp.asarray(b), qc, jnp.uint32(mu))
        s = jnp.asarray(acc) + p
        s = jnp.where(s >= (qc << 1), s - (qc << 1), s)
        return jnp.where(s >= qc, s - qc, s)
    p = mulmod_barrett(jnp.asarray(a), jnp.asarray(b), qc, jnp.uint32(mu))
    return addmod(jnp.asarray(acc), p, qc)


# ---------------------------------------------- multi-prime bank oracles

def ntt_fwd_banks_ref(x, qs, tw, twp, pre, prep, negacyclic: bool,
                      lazy: bool = False, reduce_out: bool = True):
    """vmap over the prime axis: x (k, ..., n), per-prime tables stacked
    on axis 0 (the TablePack layout).  Same math as the banks kernel —
    in lazy reduce_out=False mode the op SEQUENCE mirrors the kernel
    exactly, so even the [0, 2q) representatives match bit-for-bit."""

    def per(xi, q, twi, twpi, ps, psp):
        # q keeps the pack's element dtype (u32 CKKS rows, u16 small
        # rings) so the modmath dtype dispatch sees matching lanes
        if negacyclic:
            xi = (mulmod_shoup_lazy if lazy else mulmod_shoup)(xi, ps, psp, q)
        return _ntt.cg_ntt(xi, twi, twpi, q, unroll=2, lazy=lazy,
                           reduce_out=reduce_out)

    return jax.vmap(per)(x, qs, tw, twp, pre, prep)


def ntt_inv_banks_ref(x, qs, ninv, ninv_p, itw, itwp, post, postp,
                      negacyclic: bool, lazy: bool = False,
                      reduce_out: bool = True):
    def per(xi, q, nv, nvp, itwi, itwpi, ips, ipsp):
        xi = _ntt.cg_intt(xi, itwi, itwpi, 0, 0, q, apply_ninv=False, unroll=2,
                          lazy=lazy, reduce_out=False)
        mul = mulmod_shoup_lazy if (lazy and not reduce_out) else mulmod_shoup
        if negacyclic:
            return mul(xi, ips, ipsp, q)                # psi^-i * n^-1 fused
        return mul(xi, nv, nvp, q)

    return jax.vmap(per)(x, qs, ninv, ninv_p, itw, itwp, post, postp)


def twiddle_mul_banks_ref(x, qs, w, wp, lazy: bool = False):
    """Per-prime weight-row multiply (the four-step twiddle correction
    and psi weights): x (k, ..., n) times rows w/wp (k, n) mod qs (k,);
    lazy emits the [0, 2q) Shoup representative."""
    ex = (1,) * (x.ndim - 2)
    k, n = w.shape
    mul = mulmod_shoup_lazy if lazy else mulmod_shoup
    return mul(x, w.reshape((k,) + ex + (n,)),
               wp.reshape((k,) + ex + (n,)),
               qs.reshape((k,) + ex + (1,)))


@functools.lru_cache(maxsize=None)
def _brev(n: int):
    """Bit-reversal permutation — an involution, so the same gather
    converts bitrev->natural and natural->bitrev."""
    return bitrev_perm(n)


def fourstep_dims(fp: dict) -> tuple[int, int]:
    """(n1, n2) of a four-step pack, read from static table shapes (the
    pack holds no Python ints so it can ride through jit as a pytree)."""
    return fp["pack1"]["tw"].shape[-1] * 2, fp["pack2"]["tw"].shape[-1] * 2


def _fourstep_rows(fp: dict, k: int):
    """The first k prime rows of a FourStepPack's multi-pass tables."""
    rows = lambda t, *names: tuple(t[name][:k] for name in names)
    return (rows(fp, "qs")[0], rows(fp["pack1"], *_PASS_KEYS),
            rows(fp["pack2"], *_PASS_KEYS))


_PASS_KEYS = ("qs", "tw", "twp", "itw", "itwp", "ninv", "ninv_p", "psi",
              "psip", "ipsin", "ipsinp")


def _fwd_pass(x, t, lazy, reduce_out):
    qs, tw, twp, _, _, _, _, psi, psip, _, _ = t
    return ntt_fwd_banks_ref(x, qs, tw, twp, psi, psip, False, lazy=lazy,
                             reduce_out=reduce_out)


def _inv_pass(x, t, lazy, reduce_out):
    qs, _, _, itw, itwp, ninv, ninv_p, _, _, ipsin, ipsinp = t
    return ntt_inv_banks_ref(x, qs, ninv, ninv_p, itw, itwp, ipsin, ipsinp,
                             False, lazy=lazy, reduce_out=reduce_out)


def ntt_fourstep_banks_ref(x, fp: dict, negacyclic: bool, lazy: bool = False):
    """The four-step forward transform as separate passes (the plain
    reference of ``ntt_kernel.fourstep_fwd_banks_pallas``): [psi
    pre-weight] -> NTT-N1 over the columns (the N2 columns fold into the
    batch) -> bit-reversal gather -> w^(j2*k1) twiddle -> NTT-N2 over
    the rows -> gather -> transposed readout A_hat[k2*n1 + k1].  x:
    (k, ..., n) u32, fp a FourStepPack for at least k primes.  In lazy
    mode the inter-pass values ride in [0, 2q) and the step-3 Shoup
    product absorbs them; the output is canonical either way."""
    x = jnp.asarray(x)
    k = x.shape[0]
    n1, n2 = fourstep_dims(fp)
    n = n1 * n2
    qs, t1, t2 = _fourstep_rows(fp, k)
    shape = x.shape
    x = x.reshape(k, -1, n)
    b = x.shape[1]
    if negacyclic:
        x = twiddle_mul_banks_ref(x, qs, fp["psi"][:k], fp["psip"][:k],
                                  lazy=lazy)
    xt = x.reshape(k, b, n1, n2).swapaxes(-1, -2).reshape(k, b * n2, n1)
    xt = _fwd_pass(xt, t1, lazy, reduce_out=False)[..., _brev(n1)]
    x = xt.reshape(k, b, n2, n1).swapaxes(-1, -2).reshape(k, b, n)
    x = twiddle_mul_banks_ref(x, qs, fp["tw"][:k], fp["twp"][:k], lazy=lazy)
    xr = _fwd_pass(x.reshape(k, b * n1, n2), t2, lazy,
                   reduce_out=True)[..., _brev(n2)]
    return xr.reshape(k, b, n1, n2).swapaxes(-1, -2).reshape(shape)


def intt_fourstep_banks_ref(x, fp: dict, negacyclic: bool,
                            lazy: bool = False):
    """Inverse of ``ntt_fourstep_banks_ref`` (natural-order input), as
    separate passes: each sub-iNTT contributes 1/Ni, the negacyclic
    psi^-i post-weight (or pass 1's ninv epilogue) fully reduces."""
    x = jnp.asarray(x)
    k = x.shape[0]
    n1, n2 = fourstep_dims(fp)
    n = n1 * n2
    qs, t1, t2 = _fourstep_rows(fp, k)
    shape = x.shape
    x = x.reshape(k, -1, n)
    b = x.shape[1]
    x = x.reshape(k, b, n2, n1).swapaxes(-1, -2)            # (k, b, n1, n2)
    xr = _inv_pass(x.reshape(k, b * n1, n2)[..., _brev(n2)], t2, lazy,
                   reduce_out=False)
    x = twiddle_mul_banks_ref(xr.reshape(k, b, n), qs, fp["itw"][:k],
                              fp["itwp"][:k], lazy=lazy)
    xt = (x.reshape(k, b, n1, n2).swapaxes(-1, -2)
          .reshape(k, b * n2, n1)[..., _brev(n1)])
    xt = _inv_pass(xt, t1, lazy, reduce_out=not negacyclic)
    x = xt.reshape(k, b, n2, n1).swapaxes(-1, -2).reshape(k, b, n)
    if negacyclic:
        x = twiddle_mul_banks_ref(x, qs, fp["ipsi"][:k], fp["ipsip"][:k])
    return x.reshape(shape)


def galois_banks_ref(x, idx):
    """NTT-domain Galois automorphism: a pure gather along the lane axis,
    identical for every prime row (see ``core.params.galois_eval_perm``).
    x: (k, ..., n); idx: (n,) int32, or (B, n) per-batch gather rows
    aligned with x's (k, B, n) middle axis."""
    x = jnp.asarray(x)
    idx = jnp.asarray(idx)
    if idx.ndim == 2:
        return jnp.take_along_axis(x, idx[None].astype(jnp.int32), axis=-1)
    return jnp.take(x, idx, axis=-1)


def galois_digits_banks_ref(x, idx):
    """Digit-extension gather (the hoisted-rotation move): x (d, k, B, n)
    key-switch digit stacks, idx (B, n) per-batch gather rows shared by
    every digit and prime row.  out[d, p, b, j] = x[d, p, b, idx[b, j]].
    A (d, k, 1, n) x against a (B, n) idx broadcasts the ONE shared
    digit stack over every gather row (the hoisted decompose-once
    layout): out[d, p, b, j] = x[d, p, 0, idx[b, j]]."""
    x = jnp.asarray(x)
    idx = jnp.asarray(idx, jnp.int32)
    if x.shape[2] == 1 and idx.shape[0] != 1:
        return jnp.take(x[:, :, 0], idx, axis=-1)
    return jnp.take_along_axis(x, idx[None, None], axis=-1)


def dyadic_basemul_banks_ref(a, b, qs, mus, gamma, gammap,
                             lazy: bool = False):
    """Degree-1 basecase multiplication of an incomplete ring (block=2):
    a, b (k, ..., n) canonical NTT-domain operands, gamma/gammap (k, n/2)
    per-pair ζ factors.  Mirrors the kernel's exact op sequence
    (Barrett for var×var, Shoup for γ, lazy band accumulate)."""
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    n = a.shape[-1]
    h = n // 2
    ex = (1,) * (a.ndim - 2)
    k = qs.shape[0]
    q = qs.reshape((k,) + ex + (1,))
    mu = mus.reshape((k,) + ex + (1,))
    g = gamma.reshape((k,) + ex + (h,))
    gp = gammap.reshape((k,) + ex + (h,))
    a0, a1 = a[..., :h], a[..., h:]
    b0, b1 = b[..., :h], b[..., h:]
    if lazy:
        q2 = q + q
        t = mulmod_shoup_lazy(mulmod_barrett_lazy(a1, b1, q, mu), g, gp, q)
        s0 = mulmod_barrett_lazy(a0, b0, q, mu) + t
        c0 = jnp.where(s0 >= q2, s0 - q2, s0)
        s1 = mulmod_barrett_lazy(a0, b1, q, mu) \
            + mulmod_barrett_lazy(a1, b0, q, mu)
        c1 = jnp.where(s1 >= q2, s1 - q2, s1)
        c0 = jnp.where(c0 >= q, c0 - q, c0)
        c1 = jnp.where(c1 >= q, c1 - q, c1)
    else:
        t = mulmod_shoup(mulmod_barrett(a1, b1, q, mu), g, gp, q)
        s0 = mulmod_barrett(a0, b0, q, mu) + t
        c0 = jnp.where(s0 >= q, s0 - q, s0)
        s1 = mulmod_barrett(a0, b1, q, mu) + mulmod_barrett(a1, b0, q, mu)
        c1 = jnp.where(s1 >= q, s1 - q, s1)
    return jnp.concatenate([c0, c1], axis=-1)


def dyadic_inner_banks_ref(ext, evk, qs, mus, lazy: bool = False):
    """ext: (d, k, B, n); evk: (d, k, n) shared or (d, k, B, n) per-batch
    key digits; qs/mus: (k,).  Accumulates the digit products in the
    same order as the fused kernel (exact match, both modes)."""
    q = qs[:, None, None]
    mu = mus[:, None, None]
    evk_b = evk if evk.ndim == 4 else evk[:, :, None, :]
    if lazy:
        prods = mulmod_barrett_lazy(ext, evk_b, q[None], mu[None])

        def body(acc, p):
            return lazy_addmod(acc, p, q), None

        acc, _ = jax.lax.scan(body, prods[0], prods[1:])
        return jnp.where(acc >= q, acc - q, acc)
    prods = mulmod_barrett(ext, evk_b, q[None], mu[None])

    def body(acc, p):
        return addmod(acc, p, q), None

    acc, _ = jax.lax.scan(body, prods[0], prods[1:])
    return acc


def base_conv_banks_ref(x, src, inv, inv_p, dst, f, f_p, off):
    """Centred fast base conversion (``kernels.base_conv_kernel``): x
    (d, m, B, n) source rows, slot (j, i) mod src[j, i]; src/inv/inv_p
    (d, m); dst (t,); f/f_p/off (d, m, t).  Returns (t, d, B, n), every
    value canonical, as the kernel's."""
    q = dst[:, None, None, None]
    y = mulmod_shoup(x, inv[..., None, None], inv_p[..., None, None],
                     src[..., None, None])
    neg = y > (src >> 1)[..., None, None]
    acc = jnp.zeros((dst.shape[0],) + y[:, 0].shape, y.dtype)
    for i in range(x.shape[1]):
        col = lambda a: a[:, i].T[..., None, None]             # (t, d, 1, 1)
        yt = jnp.where(neg[None, :, i], y[None, :, i] + col(off), y[None, :, i])
        acc = addmod(acc, mulmod_shoup(yt, col(f), col(f_p), q), q)
    return acc
