"""Pointwise ("Dyadic Mod", paper Fig 22 / Table I) modular kernels.

Ciphertext-by-ciphertext products have no precomputed operand, so the
Shoup trick does not apply; these use the u32-limb Barrett reduction
(the paper's CMOS-coprocessor op, here a first-class TPU kernel).

Kernels:
  * ``dyadic_mul``  — c = a .* b mod q
  * ``dyadic_mac``  — acc' = acc + a .* b mod q  (key-switch inner loop:
    the MM/MA array of paper Fig 22, fused so the accumulator never
    leaves VMEM)
  * ``dyadic_inner_banks`` — out[j] = sum_i ext[i, j] .* evk[i, j] mod
    q_j: the WHOLE key-switch digit inner product for one prime bank in
    a single program.  Grid (prime, batch_tile, lane_chunk); the digit
    loop is unrolled inside the kernel so the accumulator stays in VMEM
    across all digits (the paper's pipelined MM -> MA chain).
  * ``dyadic_basemul_banks`` — the degree-1 basecase multiplication of
    an INCOMPLETE ring (``core.ringspec.RingSpec`` with block=2, e.g.
    ML-KEM): pair j of the NTT domain is (x[j], x[j+n/2]) and products
    are (a0+a1·X)(b0+b1·X) mod (X² − γ_j) with per-pair ζ factors γ.

Lanes are always u32; ``bits`` selects the Barrett form (see
core.modmath): 32 uses the limb mulhi, 16 (q in (2^10, 2^12)) an exact
u32 product with the (2^10, 2^12) window constants.  Per-prime scalars
(q, mu) ride whole in SMEM; per-prime rows use a (k, 1, n) layout so a
(1, 1, n) block satisfies the TPU block rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.modmath import _barrett16_lazy_u32, mulhi_u32
from repro.kernels import resolve_interpret
from repro.kernels.ntt_kernel import U32, _shoup, _shoup_lazy, _smem, lane_block


def _barrett_lazy(a, b, q, mu, bits):
    """[0, 2q) Barrett product: one conditional subtract of 2q; the MAC
    digit loop accumulates these and reduces once in its epilogue."""
    if bits == 16:
        r = _barrett16_lazy_u32(a, b, q, mu)
    else:
        lo = a * b
        approx = (mulhi_u32(a, b) << 3) | (lo >> 29)
        qhat = (mulhi_u32(approx, mu) << 1) | ((approx * mu) >> 31)
        r = lo - qhat * q
    q2 = q + q
    return jnp.where(r >= q2, r - q2, r)


def _barrett(a, b, q, mu, bits):
    r = _barrett_lazy(a, b, q, mu, bits)
    return jnp.where(r >= q, r - q, r)


def _mul_kernel(a_ref, b_ref, o_ref, *, q: int, mu: int, lazy: bool):
    qc = U32(q)
    muc = U32(mu)
    if lazy:
        r = _barrett_lazy(a_ref[...], b_ref[...], qc, muc, 32)
        o_ref[...] = jnp.where(r >= qc, r - qc, r)
    else:
        o_ref[...] = _barrett(a_ref[...], b_ref[...], qc, muc, 32)


def _mac_kernel(acc_ref, a_ref, b_ref, o_ref, *, q: int, mu: int, lazy: bool):
    qc = U32(q)
    if lazy:
        # acc in [0, q), product in [0, 2q): sum < 3q, two-step reduce
        p = _barrett_lazy(a_ref[...], b_ref[...], qc, U32(mu), 32)
        s = acc_ref[...] + p
        s = jnp.where(s >= (qc << 1), s - (qc << 1), s)
    else:
        p = _barrett(a_ref[...], b_ref[...], qc, U32(mu), 32)
        s = acc_ref[...] + p
    o_ref[...] = jnp.where(s >= qc, s - qc, s)


def _tile_call(kernel, args, *, tile: int, interpret: bool | None):
    interpret = resolve_interpret(interpret)
    b, n = args[0].shape
    assert b % tile == 0
    spec = pl.BlockSpec((tile, n), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(b // tile,),
        in_specs=[spec] * len(args),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b, n), args[0].dtype),
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("q", "mu", "tile", "lazy", "interpret"))
def dyadic_mul(a, b, *, q: int, mu: int, tile: int = 8, lazy: bool = False,
               interpret: bool | None = None):
    kern = functools.partial(_mul_kernel, q=q, mu=mu, lazy=lazy)
    return _tile_call(kern, [a, b], tile=tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("q", "mu", "tile", "lazy", "interpret"))
def dyadic_mac(acc, a, b, *, q: int, mu: int, tile: int = 8, lazy: bool = False,
               interpret: bool | None = None):
    kern = functools.partial(_mac_kernel, q=q, mu=mu, lazy=lazy)
    return _tile_call(kern, [acc, a, b], tile=tile, interpret=interpret)


# ------------------------------------------------ multi-prime inner product

def _inner_banks_kernel(ext_ref, evk_ref, q_ref, mu_ref, o_ref, *, digits: int,
                        lazy: bool):
    """Program (p, i, c): acc = sum_d ext[d] .* evk[d] mod q_p over all
    ``digits`` digit rows, accumulator VMEM-resident throughout.  The
    evk block is either (d, 1, 1, nl) — one key row broadcast over the
    batch tile — or (d, 1, tile, nl) — per-batch-element key digits;
    both broadcast against the (tile, nl) ext rows.

    Lazy mode keeps products AND the accumulator in [0, 2q) — one
    conditional select per digit instead of two (plus the saved Barrett
    subtract) — and reduces exactly once in the epilogue."""
    p = pl.program_id(0)
    q = q_ref[p]
    mu = mu_ref[p]
    if lazy:
        q2 = q << 1
        acc = _barrett_lazy(ext_ref[0, 0], evk_ref[0, 0], q, mu, 32)
        for d in range(1, digits):
            prod = _barrett_lazy(ext_ref[d, 0], evk_ref[d, 0], q, mu, 32)
            s = acc + prod                              # < 4q < 2^32
            acc = jnp.where(s >= q2, s - q2, s)
        acc = jnp.where(acc >= q, acc - q, acc)         # epilogue
    else:
        acc = _barrett(ext_ref[0, 0], evk_ref[0, 0], q, mu, 32)
        for d in range(1, digits):
            prod = _barrett(ext_ref[d, 0], evk_ref[d, 0], q, mu, 32)
            s = acc + prod
            acc = jnp.where(s >= q, s - q, s)
    o_ref[0] = acc


@functools.partial(jax.jit, static_argnames=("digits", "tile", "lazy", "interpret"))
def dyadic_inner_banks(ext, evk, qs, mus, *, digits: int, tile: int = 8,
                       lazy: bool = False, interpret: bool | None = None):
    """ext: (d, k, batch, n) NTT-domain digit extensions; evk: (d, k, n)
    key digits shared by the whole batch, or (d, k, batch, n) per-batch
    key digits (a ciphertext batch mixing Galois keys); qs/mus: (k,)
    per-prime modulus/Barrett constants.  Returns (k, batch, n): the
    key-switch accumulator over all digits."""
    interpret = resolve_interpret(interpret)
    d, k, b, n = ext.shape
    assert d == digits and b % tile == 0
    nl = lane_block(n)
    if evk.ndim == 4:
        assert evk.shape == (d, k, b, n)
        evk_spec = pl.BlockSpec((d, 1, tile, nl), lambda p, i, c: (0, p, i, c))
    else:
        evk = evk[:, :, None, :]                        # (d, k, 1, n)
        evk_spec = pl.BlockSpec((d, 1, 1, nl), lambda p, i, c: (0, p, 0, c))
    kern = functools.partial(_inner_banks_kernel, digits=digits, lazy=lazy)
    return pl.pallas_call(
        kern,
        grid=(k, b // tile, n // nl),
        in_specs=[
            pl.BlockSpec((d, 1, tile, nl), lambda p, i, c: (0, p, i, c)),
            evk_spec, _smem(), _smem(),
        ],
        out_specs=pl.BlockSpec((1, tile, nl), lambda p, i, c: (p, i, c)),
        out_shape=jax.ShapeDtypeStruct((k, b, n), ext.dtype),
        interpret=interpret,
    )(ext, evk, qs, mus)


# ----------------------------------------- incomplete-ring basecase mul

def _basemul_banks_kernel(a_ref, b_ref, q_ref, mu_ref, g_ref, gp_ref,
                          o_ref, *, lazy: bool, bits: int):
    """Program (p, i): degree-1 residue products for one batch tile.

    Pair j of the CG-ordered NTT domain is (x[j], x[j+n/2]); the product
    mod (X² − γ_j) is

        c0[j] = a0·b0 + γ_j·(a1·b1)      c1[j] = a0·b1 + a1·b0

    Variable×variable products use Barrett (no precomputed operand);
    the γ_j multiply is Shoup (g/gp are the precomputed per-pair rows).
    Lazy mode accumulates in the [0, 2q) band and the epilogue always
    reduces to [0, q) (the basecase ends the transform, so there is no
    lazy consumer)."""
    p = pl.program_id(0)
    q = q_ref[p]
    mu = mu_ref[p]
    a = a_ref[0]                        # (tile, n)
    b = b_ref[0]
    h = a.shape[-1] // 2
    a0, a1 = a[:, :h], a[:, h:]
    b0, b1 = b[:, :h], b[:, h:]
    g = g_ref[0]                        # (1, h) γ row
    gp = gp_ref[0]
    if lazy:
        q2 = q + q
        t = _shoup_lazy(_barrett_lazy(a1, b1, q, mu, bits), g, gp, q, bits)
        s0 = _barrett_lazy(a0, b0, q, mu, bits) + t      # < 4q
        c0 = jnp.where(s0 >= q2, s0 - q2, s0)
        s1 = _barrett_lazy(a0, b1, q, mu, bits) \
            + _barrett_lazy(a1, b0, q, mu, bits)
        c1 = jnp.where(s1 >= q2, s1 - q2, s1)
        c0 = jnp.where(c0 >= q, c0 - q, c0)              # epilogue
        c1 = jnp.where(c1 >= q, c1 - q, c1)
    else:
        t = _shoup(_barrett(a1, b1, q, mu, bits), g, gp, q, bits)
        s0 = _barrett(a0, b0, q, mu, bits) + t
        c0 = jnp.where(s0 >= q, s0 - q, s0)
        s1 = _barrett(a0, b1, q, mu, bits) + _barrett(a1, b0, q, mu, bits)
        c1 = jnp.where(s1 >= q, s1 - q, s1)
    o_ref[0] = jnp.concatenate([c0, c1], axis=-1)


@functools.partial(jax.jit, static_argnames=("tile", "lazy", "bits", "interpret"))
def dyadic_basemul_banks(a, b, qs, mus, gamma, gammap, *, tile: int = 8,
                         lazy: bool = False, bits: int = 32,
                         interpret: bool | None = None):
    """a, b: (k, batch, n) NTT-domain operands of an incomplete ring
    (canonical [0, q) inputs); qs/mus: (k,); gamma/gammap: (k, n/2)
    per-pair ζ factors + Shoup companions.  Returns (k, batch, n) u32."""
    interpret = resolve_interpret(interpret)
    k, bb, n = a.shape
    assert a.shape == b.shape and bb % tile == 0
    u = lambda x: x.astype(U32)
    blk = pl.BlockSpec((1, tile, n), lambda p, i: (p, i, 0))
    row = pl.BlockSpec((1, 1, n // 2), lambda p, i: (p, 0, 0))
    return pl.pallas_call(
        functools.partial(_basemul_banks_kernel, lazy=lazy, bits=bits),
        grid=(k, bb // tile),
        in_specs=[blk, blk, _smem(), _smem(), row, row],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((k, bb, n), U32),
        interpret=interpret,
    )(u(a), u(b), u(qs), u(mus), u(gamma)[:, None, :], u(gammap)[:, None, :])
