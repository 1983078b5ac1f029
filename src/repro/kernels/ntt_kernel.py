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

Three kernel families live here:

* Single-prime (``ntt_fwd_pallas`` / ``ntt_inv_pallas``): grid over
  batch tiles only; the modulus and all derived constants are static.

* Multi-prime "NTT banks" (``ntt_fwd_banks_pallas`` /
  ``ntt_inv_banks_pallas``): the paper's Fig 22 bank array, where 8
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

  VMEM per program at n = 128: a 512-row block of 256 KiB in and
  256 KiB out, each double-buffered (1 MiB), plus lane twiddles
  2*7*128*4 = 7 KiB.

* The large-N four-step transform in one kernel per direction
  (``fourstep_fwd_banks_pallas`` / ``fourstep_inv_banks_pallas``, see
  that section): grid (prime, block of whole polynomials), both passes,
  the twiddle correction, the transposition and the bit reversals in
  VMEM.  A 2^14 block is 4 polynomials (256 KiB), a 2^15 block 2.
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
from repro.core.params import bitrev
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

def _ct(a, b, w, wp, q, lazy, bits):
    """Cooley-Tukey butterfly: (a + w*b, a - w*b)."""
    if lazy:
        q2 = q + q
        t = _shoup_lazy(b, w, wp, q, bits)
        return _lazy_add(a, t, q2), _lazy_sub(a, t, q2)
    t = _shoup(b, w, wp, q, bits)
    return _addmod(a, t, q), _submod(a, t, q)


def _gs(a, b, w, wp, q, lazy, bits):
    """Gentleman-Sande butterfly: (a + b, (a - b)*w)."""
    if lazy:
        q2 = q + q
        return (_lazy_add(a, b, q2),
                _shoup_lazy(_lazy_sub(a, b, q2), w, wp, q, bits))
    return _addmod(a, b, q), _shoup(_submod(a, b, q), w, wp, q, bits)


def _fwd_stages(x, consts, q, lazy, bits):
    for m, w, wp, is_lo, a_is_partner in consts:
        y = _partner(x, m, a_is_partner)
        hi = jnp.where(is_lo, y, x)
        lo = jnp.where(is_lo, x, y)
        x = jnp.where(is_lo, *_ct(lo, hi, w, wp, q, lazy, bits))
    return x


def _inv_stages(x, consts, q, lazy, bits):
    for m, w, wp, is_lo, a_is_partner in reversed(consts):
        y = _partner(x, m, a_is_partner)
        e = jnp.where(is_lo, x, y)
        o = jnp.where(is_lo, y, x)
        x = jnp.where(is_lo, *_gs(e, o, w, wp, q, lazy, bits))
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


# ---------------------------------------------- elementwise lane blocks

# Lane block of the elementwise banks kernels: a 2^14 ring row is split
# into 2048-lane chunks so a program's double-buffered blocks stay far
# below the scoped VMEM limit at any legal batch tile.
LANE_BLOCK = 2048


def lane_block(n: int) -> int:
    return min(n, LANE_BLOCK)


# ---------------------------------------- four-step NTT in one kernel
#
# The large-N transform (paper §IX) runs whole polynomials through ONE
# program: a block of polynomials is read once from HBM in its natural
# (n1, n2) layout (x[j1*n2 + j2] at row j1, lane j2), transformed in
# VMEM, and written once in the natural frequency order
# A_hat[k2*n1 + k1], which is the (n2, n1) layout.  Between the two
# passes the block is transposed in VMEM, so both passes run along the
# row (sublane) axis:
#
#   rows in bit-reversed order -> psi pre-weight -> DIT network along
#   the n1 rows (natural k1 out) -> step-3 twiddle w^(j2*k1) ->
#   transpose -> DIF network along the n2 rows (bit-reversed k2 out) ->
#   rows back in natural order
#
# and the inverse mirrors it (DIT with the inverse root along n2, the
# inverse twiddle, DIF along n1, then psi^-i * n^-1).  A row network
# pairs whole vregs at distances of 8 rows or more, so each of those
# stages multiplies n/2 values and needs no rotation; below 8 rows it
# pairs sublanes by rolls.  The bit reversal of the rows is a strided
# load or store of 8-row groups plus one fixed sublane permutation.
# Values (the lazy [0, 2q) band between stages included) never leave
# VMEM, and the reduced output equals the multi-pass reference
# (``ref.ntt_fourstep_banks_ref``) bit for bit.

def _row_stage_offsets(r: int) -> list[tuple[int, int, int]]:
    """(m, first table row, table rows) of each stage of an r-row
    network, pair distance m = 1, 2, ..., r/2 (the DIT order; DIF runs
    them reversed).  A stage with m >= 8 has one table row per pair
    index i < m; a stage inside a vreg has one per sublane s (pair
    index s mod m).  r + 16 rows in all."""
    assert r >= SUBLANES and r & (r - 1) == 0, r
    out, off, m = [], 0, 1
    while m < r:
        rows = max(m, SUBLANES)
        out.append((m, off, rows))
        off, m = off + rows, 2 * m
    return out


def row_twiddles(root: int, q: int, r: int) -> np.ndarray:
    """(r + 16,) twiddle column of the r-row networks for a primitive
    r-th root of unity mod q: stage m's row for pair index i holds
    root^(i * r / (2m)).  The DIT and the DIF network of one root share
    it."""
    col = []
    for m, _, rows in _row_stage_offsets(r):
        step = pow(root, r // (2 * m), q)
        col += [pow(step, i % m, q) for i in range(rows)]
    return np.array(col, dtype=np.uint32)


def _sublane_iota(lanes: int):
    return lax.broadcasted_iota(jnp.int32, (SUBLANES, lanes), 0)


def _brev8_masks(lanes: int):
    """Masks of the fixed sublane permutation s -> brev3(s) (it swaps
    sublanes 1 and 4, 3 and 6): where a roll by 3, or by 5, brings the
    row that belongs there (read off the rolled iota, so they hold
    whichever direction the hardware rotate takes)."""
    s = _sublane_iota(lanes)
    want = ((s & 1) << 2) | (s & 2) | ((s >> 2) & 1)
    return pltpu.roll(s, 3, 0) == want, pltpu.roll(s, 5, 0) == want


def _brev8(x, masks):
    """x (..., 8, L): out[..., s, :] = x[..., brev3(s), :]."""
    by3, by5 = masks
    ax = x.ndim - 2
    return jnp.where(by3, pltpu.roll(x, 3, ax),
                     jnp.where(by5, pltpu.roll(x, 5, ax), x))


def _brev_load(x_ref, r: int, lanes: int):
    """The (1, g, r*c, lanes/c) block, c = lanes/128 chunks per row
    (strided access needs 128-lane rows; c = 1 up to 128 lanes), as a
    (g, r, lanes) value whose row p is row brev(p) of the block: 8-row
    group a is the strided load of rows brev(a) + (r/8)*u, u < 8, with
    its sublanes bit-reversed."""
    g, c = x_ref.shape[1], x_ref.shape[2] // r
    v = r // SUBLANES
    lg = v.bit_length() - 1

    def group(a):
        parts = [x_ref[0, :, pl.ds(c * bitrev(a, lg) + h, SUBLANES,
                                   stride=c * v), :] for h in range(c)]
        return jnp.concatenate(parts, axis=-1) if c > 1 else parts[0]

    x = jnp.stack([group(a) for a in range(v)], axis=1)
    return _brev8(x, _brev8_masks(lanes)).reshape(g, r, lanes)


def _brev_store(o_ref, x):
    """Inverse of ``_brev_load``: row p of x (g, r, lanes) goes to row
    brev(p) of the (1, g, r*c, lanes/c) block."""
    g, r, lanes = x.shape
    c = o_ref.shape[2] // r
    w = lanes // c
    v = r // SUBLANES
    lg = v.bit_length() - 1
    x = _brev8(x.reshape(g, v, SUBLANES, lanes), _brev8_masks(lanes))
    for a in range(v):
        for h in range(c):
            o_ref[0, :, pl.ds(c * bitrev(a, lg) + h, SUBLANES,
                              stride=c * v), :] = x[:, a, :, h * w:(h + 1) * w]


def _row_stages(x, t_ref, tp_ref, q, lazy: bool, dit: bool):
    """In-place radix-2 network along the rows of x (g, r, L), one
    transform per lane.  dit: Cooley-Tukey from bit-reversed to natural
    row order (m = 1 ... r/2); else Gentleman-Sande from natural to
    bit-reversed (m = r/2 ... 1).  The (1, r + 16, L) table refs hold
    ``row_twiddles`` broadcast over the lanes."""
    g, r, lanes = x.shape
    bfly = _ct if dit else _gs
    stages = _row_stage_offsets(r)
    s = _sublane_iota(lanes)
    for m, off, rows in (stages if dit else stages[::-1]):
        w = t_ref[0, off:off + rows, :]
        wp = tp_ref[0, off:off + rows, :]
        if m >= SUBLANES:            # pairs of whole vregs
            xs = x.reshape(g, r // (2 * m), 2, m, lanes)
            a, b = bfly(xs[:, :, 0], xs[:, :, 1], w, wp, q, lazy, 32)
            x = jnp.stack([a, b], axis=2).reshape(g, r, lanes)
            continue
        # pairs inside a vreg: sublane s with bit m clear holds a, s ^ m
        # holds b; the partner comes by sublane rolls
        x4 = x.reshape(g, r // SUBLANES, SUBLANES, lanes)
        is_lo = (s & m) == 0
        if 2 * m == SUBLANES:
            y = pltpu.roll(x4, m, 2)
        else:
            y = jnp.where(pltpu.roll(s, m, 0) == (s ^ m), pltpu.roll(x4, m, 2),
                          pltpu.roll(x4, SUBLANES - m, 2))
        a = jnp.where(is_lo, x4, y)
        b = jnp.where(is_lo, y, x4)
        x = jnp.where(is_lo, *bfly(a, b, w, wp, q, lazy, 32)).reshape(g, r, lanes)
    return x


def _fourstep_fwd_kernel(x_ref, q_ref, *refs, negacyclic: bool, lazy: bool):
    if negacyclic:
        pre_ref, prep_ref, *refs = refs
    a_ref, ap_ref, tw_ref, twp_ref, c_ref, cp_ref, o_ref = refs
    qc = q_ref[pl.program_id(0)]
    mul = _shoup_lazy if lazy else _shoup
    n1, n2 = tw_ref.shape[1:]
    x = _brev_load(x_ref, n1, n2)                           # (g, n1, n2)
    if negacyclic:
        x = mul(x, pre_ref[0], prep_ref[0], qc, 32)
    x = _row_stages(x, a_ref, ap_ref, qc, lazy, dit=True)
    x = mul(x, tw_ref[0], twp_ref[0], qc, 32)
    x = _row_stages(jnp.swapaxes(x, 1, 2), c_ref, cp_ref, qc, lazy, dit=False)
    if lazy:
        x = jnp.where(x >= qc, x - qc, x)
    _brev_store(o_ref, x)                                   # (g, n2, n1)


def _fourstep_inv_kernel(x_ref, q_ref, ninv_ref, ninvp_ref, *refs,
                         negacyclic: bool, lazy: bool):
    if negacyclic:
        post_ref, postp_ref, *refs = refs
    c_ref, cp_ref, tw_ref, twp_ref, a_ref, ap_ref, o_ref = refs
    p = pl.program_id(0)
    qc = q_ref[p]
    n1, n2 = tw_ref.shape[1:]
    x = _row_stages(_brev_load(x_ref, n2, n1), c_ref, cp_ref, qc, lazy,
                    dit=True)
    x = (_shoup_lazy if lazy else _shoup)(jnp.swapaxes(x, 1, 2), tw_ref[0],
                                          twp_ref[0], qc, 32)
    x = _row_stages(x, a_ref, ap_ref, qc, lazy, dit=False)
    # the last multiply fully reduces any u32 representative
    if negacyclic:
        x = _shoup(x, post_ref[0], postp_ref[0], qc, 32)    # psi^-i * n^-1
    else:
        x = _shoup(x, ninv_ref[p], ninvp_ref[p], qc, 32)
    _brev_store(o_ref, x)                                   # (g, n1, n2)


def fourstep_block(b: int, n: int) -> int:
    """Polynomials per program of the four-step kernels: as many as fit
    ``BLOCK_BYTES`` (at least one), at most the batch."""
    return max(1, min(b, BLOCK_BYTES // (4 * n)))


def ref_lanes(width: int) -> int:
    """Lane width of the HBM view in which the four-step kernels read or
    write rows of ``width`` values: 128-lane rows (a wider row spans
    several), or the whole row when it is narrower."""
    return min(width, 128)


def _fourstep_call(kernel, x, scalars, tables, *, dims: tuple[int, int],
                   direction: str, interpret: bool | None):
    """Grid (prime, block of polynomials) over x (k, b, n/w, w), a view
    of (k, b, r, L) rows with w = ``ref_lanes(L)``, written as the
    (k, b, n/w', w') view of (k, b, L, r).  ``dims`` = (r, L).
    ``tables`` are (k', ...) per-prime stacks (k' >= k; program p reads
    row p), ``scalars`` (k',) SMEM arrays.  A last block that the batch
    does not fill reads rows past it and its writes there are dropped.
    When tracing is on, building the program is a
    ``kernels.fourstep.block`` span carrying the geometry."""
    interpret = resolve_interpret(interpret)
    k, b, rows_in, w_in = x.shape
    r, lanes = dims
    n = r * lanes
    w_out = ref_lanes(r)
    g = fourstep_block(b, n)
    n1, n2 = dims if direction == "fwd" else dims[::-1]
    in_specs = [pl.BlockSpec((1, g, rows_in, w_in), lambda p, i: (p, i, 0, 0))]
    in_specs += [_smem() for _ in scalars]
    in_specs += [pl.BlockSpec((1,) + t.shape[1:], lambda p, i: (p, 0, 0))
                 for t in tables]
    out_block = (1, g, n // w_out, w_out)
    with obs.span("kernels.fourstep.block", cat="kernel", n1=n1, n2=n2,
                  polys=g, direction=direction, k=k, b=b):
        return pl.pallas_call(
            kernel,
            grid=(k, pl.cdiv(b, g)),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(out_block, lambda p, i: (p, i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((k, b) + out_block[2:], U32),
            interpret=interpret,
        )(x, *scalars, *tables)


@functools.partial(jax.jit, static_argnames=("negacyclic", "lazy", "interpret"))
def fourstep_fwd_banks_pallas(x, qs, pre, prep, ta, tap, tw, twp, tc, tcp, *,
                              negacyclic: bool, lazy: bool = True,
                              interpret: bool | None = None):
    """x: (k, b, n) u32 in natural order, row i of axis 0 reduced mod
    qs[i], viewed as (k, b, n/w, w) with w = ``ref_lanes(n2)`` ->
    A_hat[k2*n1 + k1] in natural order, viewed as (k, b, n/w', w') with
    w' = ``ref_lanes(n1)``.  Per-prime tables (FourStepPack): pre/prep
    (k, n1, n2) psi weights in bit-reversed row order; ta/tap
    (k, n1 + 16, n2) and tc/tcp (k, n2 + 16, n1) ``row_twiddles`` of the
    n1- and n2-point roots; tw/twp (k, n1, n2) the step-3 twiddles
    w^(j2*k1) at [k1, j2]."""
    kern = functools.partial(_fourstep_fwd_kernel, negacyclic=negacyclic,
                             lazy=lazy)
    pre_rows = [pre, prep] if negacyclic else []
    return _fourstep_call(kern, x, [qs], pre_rows + [ta, tap, tw, twp, tc, tcp],
                          dims=(tw.shape[1], tw.shape[2]), direction="fwd",
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("negacyclic", "lazy", "interpret"))
def fourstep_inv_banks_pallas(x, qs, ninv, ninvp, post, postp, tc, tcp, tw,
                              twp, ta, tap, *, negacyclic: bool,
                              lazy: bool = True,
                              interpret: bool | None = None):
    """Inverse of ``fourstep_fwd_banks_pallas``: the (k, b, n/w', w')
    view in, the (k, b, n/w, w) view out, canonical.  tc/tcp and ta/tap
    hold the inverse roots' ``row_twiddles``, tw/twp w^-(j2*k1),
    post/postp psi^-i * n^-1 in bit-reversed row order (negacyclic),
    ninv/ninvp (k,) n^-1."""
    kern = functools.partial(_fourstep_inv_kernel, negacyclic=negacyclic,
                             lazy=lazy)
    post_rows = [post, postp] if negacyclic else []
    return _fourstep_call(kern, x, [qs, ninv, ninvp],
                          post_rows + [tc, tcp, tw, twp, ta, tap],
                          dims=(tw.shape[2], tw.shape[1]), direction="inv",
                          interpret=interpret)
