"""Centred fast base conversion as a Pallas TPU kernel (hybrid key
switching with grouped digits: the mod-up of a digit of m primes, and the
mod-down's conversion from the alpha special primes).

From a group of m source primes m_1..m_m with product M, coefficient
rows x_i (mod m_i) convert to every target prime p as

    y_i = [x_i * (M/m_i)^-1]_{m_i}        centred: y_i - m_i if y_i > m_i/2
    out_p = sum_i y_i * (M/m_i)  mod p

The tables (``fhe.batched.base_conv_tables``) hold, per group j and
source slot i, the source prime and the Shoup pair of (M/m_i)^-1, and
per target p the Shoup pair of M/m_i mod p and the offset 2p - m_i that
lifts a negative (centred) y_i into [0, 2p).  A group shorter than the
longest is padded with zero rows and zero weights, which add nothing, so
one kernel call converts every digit of a key switch.

Grid (group, row_block, lane_chunk).  A program reads the (m, rows,
lanes) block of its group's source rows, computes the m centred y_i
once, and writes the (targets, rows, lanes) block of every target, each
a lazily accumulated ([0, 2p)) sum of m Shoup products reduced once at
the end.  The scalars ride whole in SMEM as flat arrays indexed from the
group's program id.  The output is prime-major, (targets, groups, batch,
n), the layout the banks NTT takes next.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs
from repro.kernels import resolve_interpret
from repro.kernels.ntt_kernel import U32, _lazy_add, _shoup, _shoup_lazy, _smem

# Lanes per program.  On a TPU v5e, 512, 1024 and 2048 lanes (and batch
# tiles of 8 and 16) ran within 1% of each other at the N = 2^15 key
# switch's shapes.
LANES = 1024

# Names of the kernel's ops in a profile: the key switch's mod-up of the
# digits and its mod-down from the special primes.
MODUP = "base_conv_banks_modup"
MODDOWN = "base_conv_banks_moddown"


def lanes(n: int) -> int:
    return min(n, LANES)


def _base_conv_kernel(x_ref, src_ref, inv_ref, invp_ref, dst_ref, f_ref,
                      fp_ref, off_ref, o_ref, *, m: int, targets: int):
    j = pl.program_id(0)
    ys, negs = [], []
    for i in range(m):
        s = j * m + i
        q = src_ref[s]
        y = _shoup(x_ref[0, i], inv_ref[s], invp_ref[s], q, 32)
        ys.append(y)
        # all ones where the centred representative is negative
        negs.append(jnp.where(y > (q >> 1), U32(0xFFFFFFFF), U32(0)))
    for c in range(targets):
        p = dst_ref[c]
        p2 = p + p
        acc = None
        for i in range(m):
            s = (j * m + i) * targets + c
            term = _shoup_lazy(ys[i] + (negs[i] & off_ref[s]), f_ref[s],
                               fp_ref[s], p, 32)
            acc = term if acc is None else _lazy_add(acc, term, p2)
        o_ref[c, 0] = jnp.where(acc >= p, acc - p, acc)


@functools.partial(jax.jit, static_argnames=("tile", "name", "interpret"))
def base_conv_banks_pallas(x, src, inv, inv_p, dst, f, f_p, off, *,
                           tile: int = 8, name: str = "base_conv_banks",
                           interpret: bool | None = None):
    """x: (d, m, b, n) u32 source rows, group j's slot i reduced mod
    src[j, i] (b a multiple of ``tile``); src/inv/inv_p: (d, m);
    dst: (t,); f/f_p/off: (d, m, t).  Returns (t, d, b, n) u32; ``name``
    names the op (``MODUP``, ``MODDOWN``).  When tracing is on, building
    the program is a ``kernels.base_conv.block`` span (``m``,
    ``targets``, ``b``, ``n``, ``rows``)."""
    interpret = resolve_interpret(interpret)
    d, m, b, n = x.shape
    t = dst.shape[0]
    assert b % tile == 0
    nl = lanes(n)
    kern = functools.partial(_base_conv_kernel, m=m, targets=t)
    flat = lambda a: a.reshape(-1).astype(U32)
    with obs.span("kernels.base_conv.block", cat="kernel", m=m, targets=t,
                  b=b, n=n, rows=tile):
        return pl.pallas_call(
            kern,
            grid=(d, b // tile, n // nl),
            in_specs=[pl.BlockSpec((1, m, tile, nl),
                                   lambda j, i, c: (j, 0, i, c))]
            + [_smem() for _ in range(7)],
            out_specs=pl.BlockSpec((t, 1, tile, nl),
                                   lambda j, i, c: (0, j, i, c)),
            out_shape=jax.ShapeDtypeStruct((t, d, b, n), U32),
            interpret=interpret,
            name=name,
        )(x.astype(U32), *map(flat, (src, inv, inv_p, dst, f, f_p, off)))
