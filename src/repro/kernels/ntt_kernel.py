"""Fused NTT / iNTT as Pallas TPU kernels.

The whole ``stages``-stage transform runs inside ONE kernel invocation
per (batch-tile, n) VMEM block — the TPU analogue of the paper's 7-PE
pipeline where a polynomial streams through all stages without touching
main memory.  The ping-pong SRM banks of the paper become the automatic
double-buffering of the Pallas grid pipeline (HBM->VMEM block prefetch
overlaps compute on the previous tile).

In-place lane layout.  The constant-geometry (Pease) network of
``core.ntt`` re-interleaves the lanes after every stage
(out[2j], out[2j+1] <- x[j], x[j+n/2]); Mosaic has no lane interleave
below 128 lanes, so the kernels run the SAME butterflies on a permuted
layout instead: at stage t the CG element at position i lives at lane
``rotr(i, t)`` (rotation within log2(n) bits).  The butterfly pair then
sits at lanes (p, p ^ m) with m = n >> (t+1), every stage is two lane
rotations (``pltpu.roll``) plus selects, and no data moves between
stages.  Each lane carries the twiddle of its pair, so the (s, n/2) CG
tables expand to (s, n) per-lane rows (``lane_twiddles``).  Complete
transforms end with the CG output order unchanged (a rotation by log2 n
bits is the identity); an incomplete ring (``stages < log2 n``) ends
rotated and ``ops`` applies the fixed lane permutation
(``incomplete_perm``).  Values — including the lazy [0, 2q)
representatives — are bit-identical to ``core.ntt`` element for element.

Lanes are always u32.  ``bits`` (static) selects the arithmetic:
32 for the CKKS RNS primes (16-bit-limb mulhi Shoup), 16 for small
rings such as ML-KEM's q=3329 (a 16x16 product is exact in u32, so the
Shoup high part is a plain shift).  Callers narrow to u16 only at their
byte boundary.

Two kernel families live here:

* Single-prime (``ntt_fwd_pallas`` / ``ntt_inv_pallas``): grid over
  batch tiles only; the modulus and all derived constants are static.

* Multi-prime "NTT banks" (``ntt_fwd_banks_pallas`` /
  ``ntt_inv_banks_pallas``, plus the four-step step-3 companion
  ``twiddle_mul_banks_pallas``): the paper's Fig 22 bank array, where 8
  NTT units process the RNS prime rows in parallel.  The grid is
  ``(prime, row_block)`` over the TablePack layout of
  ``fhe.batched.build_table_pack``:

    qs, ninv, ninv_p (k,)       per-prime scalars, whole arrays in SMEM;
                                program (p, i) reads entry p
    tw/twp, itw/itwp (k, s, n/2) CG twiddles + Shoup companions, expanded
                                to (k, s, n) lane rows; block (1, s, n)
    psi/psip, ipsin/ipsinp (k, n) per-coefficient weight rows, laid out
                                (k, 1, n) so the (1, 1, n) block's last
                                two dims equal the array's

  The batch tile (``kernels.autotune.clamp``: a multiple of 8 or the
  whole batch) is only the padding unit.  A program covers a block of
  ``block_rows(b, n, tile)`` rows chosen from the input's shape: the
  whole (padded) batch when its (b, n) block fits ``BLOCK_BYTES``, else
  the largest multiple of the tile that divides b and fits.  The
  program runs its block as one (g, 8, n) stack of independent slabs
  whose stage chains interleave, so the VLIW slots are not left waiting
  on one dependent chain of one vreg.  The lane iota, the per-stage
  masks and the broadcast twiddle rows are built once per program at
  (8, n) and broadcast over the stack.

VMEM per program (n=128 pass of the 2^14 four-step): a 512-row block
of 256 KiB in and 256 KiB out, each double-buffered (1 MiB), plus lane
twiddles 2*7*128*4 = 7 KiB; a [9, 32768, 128] pass is a (9, 64) grid
instead of (9, 4096) steps of one 4 KiB vreg.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core.modmath import _shoup16_lazy_u32, mulhi_u32
from repro.kernels import resolve_interpret

U32 = jnp.uint32


# --------------------------------------------------- in-kernel helpers
#
# Every operand is a u32 array; ``bits`` is the ring's arithmetic width.

def _shoup_lazy(x, w, wp, q, bits):
    """[0, 2q) Shoup product (no final subtract); x any lazy-band value."""
    if bits == 16:
        return _shoup16_lazy_u32(x, w, wp, q)
    return x * w - mulhi_u32(x, wp) * q


def _shoup(x, w, wp, q, bits):
    r = _shoup_lazy(x, w, wp, q, bits)
    return jnp.where(r >= q, r - q, r)


def _addmod(a, b, q):
    s = a + b
    return jnp.where(s >= q, s - q, s)


def _submod(a, b, q):
    return jnp.where(a >= b, a - b, a + (q - b))


def _lazy_add(a, b, q2):
    s = a + b
    return jnp.where(s >= q2, s - q2, s)


def _lazy_sub(a, b, q2):
    return jnp.where(a >= b, a - b, a + (q2 - b))


def _stage_consts(get_row, stages, shape):
    """Per-stage constants of the in-place stage loop at ``shape`` (the
    trailing (rows, n) of the values they meet), built once per program:
    the pair distance m, the stage's lane rows (w, wp) broadcast, the
    mask of lanes holding the low element of their pair, and the mask of
    lanes whose rotation by +m brings the partner (derived from rotating
    the lane iota itself, so it holds whichever direction the hardware
    rotate takes).  ``get_row(t)`` yields the (1, n) lane rows of stage
    t."""
    n = shape[-1]
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    consts = []
    for t in range(stages):
        m = n >> (t + 1)
        w, wp = get_row(t)
        consts.append((m, jnp.broadcast_to(w, shape),
                       jnp.broadcast_to(wp, shape), (lane & m) == 0,
                       pltpu.roll(lane, m, 1) == (lane ^ m)))
    return consts


def _partner(x, m, a_is_partner):
    """x[..., p ^ m] for every lane p: two lane rotations and a select."""
    n, ax = x.shape[-1], x.ndim - 1
    return jnp.where(a_is_partner, pltpu.roll(x, m, ax),
                     pltpu.roll(x, n - m, ax))


# Shared stage loops: one codepath for the single-prime and banks
# kernels.  ``x`` is (rows, n) or a stack (g, rows, n) of independent
# slabs that the constants broadcast over, so one traced op covers
# every slab and their chains interleave on the chip.  In lazy mode
# values ride in [0, 2q) (see core.modmath's lazy contract) and the
# caller owns the epilogue reduction.

def _fwd_stages(x, consts, q, lazy, bits):
    q2 = q + q
    for m, w, wp, is_lo, a_is_partner in consts:
        y = _partner(x, m, a_is_partner)
        hi = jnp.where(is_lo, y, x)
        lo = jnp.where(is_lo, x, y)
        if lazy:
            tt = _shoup_lazy(hi, w, wp, q, bits)
            x = jnp.where(is_lo, _lazy_add(lo, tt, q2), _lazy_sub(lo, tt, q2))
        else:
            tt = _shoup(hi, w, wp, q, bits)
            x = jnp.where(is_lo, _addmod(lo, tt, q), _submod(lo, tt, q))
    return x


def _inv_stages(x, consts, q, lazy, bits):
    q2 = q + q
    for m, w, wp, is_lo, a_is_partner in reversed(consts):
        y = _partner(x, m, a_is_partner)
        e = jnp.where(is_lo, x, y)
        o = jnp.where(is_lo, y, x)
        if lazy:
            x = jnp.where(is_lo, _lazy_add(e, o, q2),
                          _shoup_lazy(_lazy_sub(e, o, q2), w, wp, q, bits))
        else:
            x = jnp.where(is_lo, _addmod(e, o, q),
                          _shoup(_submod(e, o, q), w, wp, q, bits))
    return x


# ------------------------------------------------------ layout helpers

def _rotl(i: np.ndarray, r: int, bits: int) -> np.ndarray:
    r %= bits
    mask = (1 << bits) - 1
    return ((i << r) | (i >> (bits - r))) & mask if r else i


@functools.lru_cache(maxsize=None)
def _lane_index(stages: int, n: int) -> np.ndarray:
    """(stages, n) CG pair index of every lane: lane p at stage t holds
    CG position rotl(p, t), whose pair (and twiddle) is that position
    mod n/2."""
    lg = n.bit_length() - 1
    p = np.arange(n, dtype=np.int64)
    return np.stack([_rotl(p, t, lg) % (n // 2) for t in range(stages)])


def lane_twiddles(tw):
    """(..., s, n/2) CG twiddle rows -> (..., s, n) per-lane rows."""
    s, half = tw.shape[-2:]
    idx = _lane_index(s, 2 * half)
    return tw[..., np.arange(s)[:, None], idx]


@functools.lru_cache(maxsize=None)
def incomplete_perm(stages: int, n: int) -> np.ndarray:
    """Lane permutation from the kernel's output layout to the CG order
    after ``stages`` stages: cg = out[..., perm].  The identity for a
    complete transform (stages == log2 n)."""
    lg = n.bit_length() - 1
    return _rotl(np.arange(n, dtype=np.int64), lg - stages, lg)


def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


# ----------------------------------------------------------- fwd kernel

def _ntt_fwd_kernel(x_ref, tw_ref, twp_ref, pre_ref, prep_ref, o_ref, *,
                    q: int, stages: int, negacyclic: bool, lazy: bool):
    qc = U32(q)
    x = x_ref[...]                      # (bt, n)
    if negacyclic:
        x = (_shoup_lazy if lazy else _shoup)(x, pre_ref[...], prep_ref[...],
                                              qc, 32)
    consts = _stage_consts(lambda t: (tw_ref[t:t + 1, :],
                                      twp_ref[t:t + 1, :]), stages, x.shape)
    x = _fwd_stages(x, consts, qc, lazy, 32)
    if lazy:
        x = jnp.where(x >= qc, x - qc, x)   # epilogue: back to [0, q)
    o_ref[...] = x


def _ntt_inv_kernel(x_ref, itw_ref, itwp_ref, post_ref, postp_ref, o_ref, *,
                    q: int, stages: int, negacyclic: bool, ninv: int,
                    ninv_p: int, lazy: bool):
    qc = U32(q)
    x = x_ref[...]
    consts = _stage_consts(lambda t: (itw_ref[t:t + 1, :],
                                      itwp_ref[t:t + 1, :]), stages, x.shape)
    x = _inv_stages(x, consts, qc, lazy, 32)
    # the epilogue multiply fully reduces either path (_shoup takes any
    # u32 representative), so lazy costs nothing extra here
    if negacyclic:
        x = _shoup(x, post_ref[...], postp_ref[...], qc, 32)
    else:
        x = _shoup(x, U32(ninv), U32(ninv_p), qc, 32)
    o_ref[...] = x


# ------------------------------------------------------------- wrappers

def _grid_call(kernel, x, tables, row_args, *, tile: int,
               interpret: bool | None):
    """Grid over batch tiles; twiddle tables and per-coefficient weight
    rows fully VMEM-resident."""
    interpret = resolve_interpret(interpret)
    b, n = x.shape
    assert b % tile == 0
    s_tables = [pl.BlockSpec(t.shape, lambda i: (0, 0)) for t in tables]
    s_rows = [pl.BlockSpec((1, n), lambda i: (0, 0)) for _ in row_args]
    return pl.pallas_call(
        kernel,
        grid=(b // tile,),
        in_specs=[pl.BlockSpec((tile, n), lambda i: (i, 0))] + s_tables + s_rows,
        out_specs=pl.BlockSpec((tile, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n), x.dtype),
        interpret=interpret,
    )(x, *tables, *row_args)


@functools.partial(jax.jit, static_argnames=("q", "stages", "negacyclic", "tile", "lazy", "interpret"))
def ntt_fwd_pallas(x, tw, twp, pre, prep, *, q: int, stages: int,
                   negacyclic: bool, tile: int = 8, lazy: bool = False,
                   interpret: bool | None = None):
    """x: (batch, n) u32; tw/twp: (s, n/2) CG tables.  pre/prep: (1, n)
    psi-power rows (ignored when not negacyclic but still passed to
    keep one kernel signature)."""
    kern = functools.partial(_ntt_fwd_kernel, q=q, stages=stages,
                             negacyclic=negacyclic, lazy=lazy)
    return _grid_call(kern, x, [lane_twiddles(tw), lane_twiddles(twp)],
                      [pre, prep], tile=tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("q", "stages", "negacyclic", "ninv", "ninv_p", "tile", "lazy", "interpret"))
def ntt_inv_pallas(x, itw, itwp, post, postp, *, q: int, stages: int,
                   negacyclic: bool, ninv: int, ninv_p: int,
                   tile: int = 8, lazy: bool = False,
                   interpret: bool | None = None):
    kern = functools.partial(_ntt_inv_kernel, q=q, stages=stages,
                             negacyclic=negacyclic, ninv=ninv, ninv_p=ninv_p,
                             lazy=lazy)
    return _grid_call(kern, x, [lane_twiddles(itw), lane_twiddles(itwp)],
                      [post, postp], tile=tile, interpret=interpret)


# ------------------------------------------------ multi-prime NTT banks

# Geometry of the banks NTT programs, from the input's (b, n) alone: a
# program's u32 block stays within BLOCK_BYTES and runs as one stack of
# independent (8, n) slabs.  Chosen on a TPU v5e (PERF.md, Findings): at
# n = 128 the time per vreg falls with the slabs that run side by side
# (1 slab 0.68 us, 8 slabs 0.10 us, 64 slabs 0.05 us) and hardly moves
# past 64.
BLOCK_BYTES = 256 * 1024
SUBLANES = 8


def block_rows(b: int, n: int, tile: int) -> int:
    """Rows per grid step for a (k, b, n) input whose batch is padded to
    a multiple of ``tile``: the whole batch when its block fits
    ``BLOCK_BYTES``, else the largest multiple of ``tile`` that divides
    b and fits (at least ``tile``)."""
    cap = BLOCK_BYTES // (4 * n)
    if b <= cap:
        return b
    return max(r for r in range(tile, max(cap, tile) + 1, tile) if b % r == 0)


def _slabs(x_ref):
    """The (1, rows, n) block as a (g, 8, n) stack of independent slabs
    (one (rows, n) slab when rows is not a multiple of 8), and the slab
    shape."""
    rows, n = x_ref.shape[1:]
    slab = (SUBLANES if rows % SUBLANES == 0 else rows, n)
    return x_ref[0].reshape(rows // slab[0], *slab), slab


def _ntt_fwd_banks_kernel(x_ref, q_ref, tw_ref, twp_ref, pre_ref, prep_ref,
                          o_ref, *, stages: int, negacyclic: bool, lazy: bool,
                          reduce_out: bool, bits: int):
    """One bank row: program (p, i) transforms row block i under prime
    row p.  The modulus is a per-program scalar read from SMEM.

    lazy + reduce_out=False emits the raw [0, 2q) representatives for a
    lazy-aware consumer (the four-step twiddle pass absorbs the
    reduction in its own Shoup multiply)."""
    qc = q_ref[pl.program_id(0)]
    x, slab = _slabs(x_ref)
    if negacyclic:
        x = (_shoup_lazy if lazy else _shoup)(x, pre_ref[0], prep_ref[0],
                                              qc, bits)
    consts = _stage_consts(lambda t: (tw_ref[0, t:t + 1, :],
                                      twp_ref[0, t:t + 1, :]), stages, slab)
    x = _fwd_stages(x, consts, qc, lazy, bits)
    if lazy and reduce_out:
        x = jnp.where(x >= qc, x - qc, x)
    o_ref[0] = x.reshape(o_ref.shape[1:])


def _ntt_inv_banks_kernel(x_ref, q_ref, ninv_ref, ninvp_ref, itw_ref, itwp_ref,
                          post_ref, postp_ref, o_ref, *, stages: int,
                          negacyclic: bool, lazy: bool, reduce_out: bool,
                          bits: int):
    p = pl.program_id(0)
    qc = q_ref[p]
    x, slab = _slabs(x_ref)
    consts = _stage_consts(lambda t: (itw_ref[0, t:t + 1, :],
                                      itwp_ref[0, t:t + 1, :]), stages, slab)
    x = _inv_stages(x, consts, qc, lazy, bits)
    # epilogue multiply: full reduce unless a lazy consumer asked for the
    # [0, 2q) representative (reduce_out=False only makes sense in lazy
    # mode; the eager multiply is always exact)
    mul = _shoup_lazy if (lazy and not reduce_out) else _shoup
    if negacyclic:
        x = mul(x, post_ref[0], postp_ref[0], qc, bits)  # psi^-i * n^-1
    else:
        x = mul(x, ninv_ref[p], ninvp_ref[p], qc, bits)
    o_ref[0] = x.reshape(o_ref.shape[1:])


def _banks_grid_call(kernel, x, scalars, tables, rows, *, tile: int,
                     interpret: bool | None):
    """Grid (prime, row_block).  ``scalars`` are (k,) per-prime values
    (whole arrays in SMEM), ``tables`` (k, s, n) lane-twiddle stacks,
    ``rows`` (k, n) weight rows (re-laid out as (k, 1, n)).  Every
    VMEM spec selects row p of its stack via the leading grid
    coordinate, so each program sees exactly its bank's constants.  All
    operands are widened to u32 lanes here.  The block is
    ``block_rows(b, n, tile)`` rows; when tracing is on, building the
    program (tracing the kernel) is a ``kernels.ntt_banks.block`` span
    carrying the geometry."""
    interpret = resolve_interpret(interpret)
    k, b, n = x.shape
    assert b % tile == 0
    blk = block_rows(b, n, tile)
    u = lambda a: a.astype(U32)
    in_specs = [pl.BlockSpec((1, blk, n), lambda p, i: (p, i, 0))]
    in_specs += [_smem() for _ in scalars]
    in_specs += [pl.BlockSpec((1,) + t.shape[1:], lambda p, i: (p, 0, 0))
                 for t in tables]
    in_specs += [pl.BlockSpec((1, 1, r.shape[-1]), lambda p, i: (p, 0, 0))
                 for r in rows]
    with obs.span("kernels.ntt_banks.block", cat="kernel", k=k, b=b, n=n,
                  rows=blk, steps=k * (b // blk)):
        return pl.pallas_call(
            kernel,
            grid=(k, b // blk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, blk, n), lambda p, i: (p, i, 0)),
            out_shape=jax.ShapeDtypeStruct((k, b, n), U32),
            interpret=interpret,
        )(u(x), *map(u, scalars), *map(u, tables),
          *(u(r)[:, None, :] for r in rows))


@functools.partial(jax.jit, static_argnames=("stages", "negacyclic", "tile", "lazy", "reduce_out", "bits", "interpret"))
def ntt_fwd_banks_pallas(x, qs, tw, twp, pre, prep, *, stages: int,
                         negacyclic: bool, tile: int = 8, lazy: bool = False,
                         reduce_out: bool = True, bits: int = 32,
                         interpret: bool | None = None):
    """x: (k, batch, n), row i reduced mod qs[i]; qs: (k,);
    tw/twp: (k, s, n/2) CG tables; pre/prep: (k, n) psi rows.  Returns
    u32 in the kernel's lane layout (``incomplete_perm`` maps an
    incomplete ring to CG order)."""
    kern = functools.partial(_ntt_fwd_banks_kernel, stages=stages,
                             negacyclic=negacyclic, lazy=lazy,
                             reduce_out=reduce_out, bits=bits)
    return _banks_grid_call(kern, x, [qs],
                            [lane_twiddles(tw), lane_twiddles(twp)],
                            [pre, prep], tile=tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("stages", "negacyclic", "tile", "lazy", "reduce_out", "bits", "interpret"))
def ntt_inv_banks_pallas(x, qs, ninv, ninvp, itw, itwp, post, postp, *,
                         stages: int, negacyclic: bool, tile: int = 8,
                         lazy: bool = False, reduce_out: bool = True,
                         bits: int = 32, interpret: bool | None = None):
    kern = functools.partial(_ntt_inv_banks_kernel, stages=stages,
                             negacyclic=negacyclic, lazy=lazy,
                             reduce_out=reduce_out, bits=bits)
    return _banks_grid_call(kern, x, [qs, ninv, ninvp],
                            [lane_twiddles(itw), lane_twiddles(itwp)],
                            [post, postp], tile=tile, interpret=interpret)


# ------------------------------------------- four-step twiddle multiply

# Lane block of the elementwise banks kernels: a 2^14 ring row is split
# into 2048-lane chunks so a program's double-buffered blocks stay far
# below the scoped VMEM limit at any legal batch tile.
LANE_BLOCK = 2048


def lane_block(n: int) -> int:
    return min(n, LANE_BLOCK)


def _twiddle_mul_banks_kernel(x_ref, q_ref, w_ref, wp_ref, o_ref, *,
                              lazy: bool):
    """Step 3 of the four-step schedule (paper §IX): the pointwise
    w^(j2*k1) correction between the column and row NTT passes, fused as
    one (prime, batch_tile, lane_chunk) Shoup multiply.  The same kernel
    applies the negacyclic psi^i pre-weights / psi^-i post-weights.
    lazy=True emits the [0, 2q) Shoup representative; either way any
    u32 input representative is accepted."""
    mul = _shoup_lazy if lazy else _shoup
    o_ref[0] = mul(x_ref[0], w_ref[0], wp_ref[0], q_ref[pl.program_id(0)], 32)


@functools.partial(jax.jit, static_argnames=("tile", "lazy", "interpret"))
def twiddle_mul_banks_pallas(x, qs, w, wp, *, tile: int = 8,
                             lazy: bool = False,
                             interpret: bool | None = None):
    """x: (k, batch, n) u32; qs: (k,); w/wp: (k, n) weight rows + Shoup
    companions.  out[p, i, :] = x[p, i, :] * w[p, :] mod qs[p]."""
    interpret = resolve_interpret(interpret)
    k, b, n = x.shape
    assert b % tile == 0
    nl = lane_block(n)
    row = pl.BlockSpec((1, 1, nl), lambda p, i, c: (p, 0, c))
    blk = pl.BlockSpec((1, tile, nl), lambda p, i, c: (p, i, c))
    return pl.pallas_call(
        functools.partial(_twiddle_mul_banks_kernel, lazy=lazy),
        grid=(k, b // tile, n // nl),
        in_specs=[blk, _smem(), row, row],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((k, b, n), U32),
        interpret=interpret,
    )(x, qs, w[:, None, :], wp[:, None, :])
