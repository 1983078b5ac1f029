"""Large-N NTT by divide and conquer — paper §IX ("Large Scale
Implementation"), TPU-native form.

The paper composes a 2^14-point NTT from two passes of 128 NTT-128
units plus a data reorder between passes.  The mathematical content is
the four-step (Bailey) decomposition with N = N1*N2:

  1. view a as an (N1, N2) matrix, A[j1, j2] = a[j1*N2 + j2]
  2. NTT_N1 along columns (root w^N2)            -> B[k1, j2]
  3. pointwise twiddle multiply by w^(j2*k1)     -> C[k1, j2]
  4. NTT_N2 along rows (root w^N1)               -> D[k1, k2]
  and A_hat[k2*N1 + k1] = D[k1, k2].

On one device, ``fourstep_ntt``/``fourstep_intt`` dispatch to
``kernels.ops.ntt_fourstep_banks``: on a TPU one kernel runs both
passes, the step-3 twiddle and the reorder on polynomials held in VMEM
— the software form of the paper's "K NTT-128 units + reorder
network".  Off-TPU the same entry points fall back to the multi-pass
reference (see ``kernels.ops`` policy).

On a TPU mesh the reorder network becomes a collective: columns sharded
across chips -> local column NTTs + local twiddle -> **all-to-all** (one
ICI collective) -> local row NTTs.  ``fourstep_ntt_sharded`` is the
shard_map implementation; the local version is the oracle.

The negacyclic wrap (for the FHE ring Z_q[x]/(x^N+1)) pre/post-weights
with psi powers exactly like the single-kernel path.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core.modmath import mulmod_shoup, shoup_precompute
from repro.core.ntt import cg_ntt, cg_intt
from repro.core.params import NTTParams, make_ntt_params, root_of_unity, bitrev_perm
from repro.kernels import ops


def ntt_natural(x, p: NTTParams):
    """Cyclic CG-NTT permuted to natural frequency order (bitrev is an
    involution, so the same static gather converts either way)."""
    return cg_ntt(x, jnp.asarray(p.tw), jnp.asarray(p.twp), p.q)[..., bitrev_perm(p.n)]


@dataclasses.dataclass(frozen=True)
class FourStepParams:
    n: int
    n1: int
    n2: int
    q: int
    p1: NTTParams               # column transform, root w^N2
    p2: NTTParams               # row transform, root w^N1
    tw_mat: np.ndarray          # (n1, n2) w^(j2*k1)
    tw_mat_p: np.ndarray
    itw_mat: np.ndarray         # inverse twiddles
    itw_mat_p: np.ndarray
    psi_mat: np.ndarray         # (n1, n2) psi^(j1*N2+j2) — negacyclic pre-weight
    psi_mat_p: np.ndarray
    ipsi_mat: np.ndarray        # psi^-i (n^-1 folded in)
    ipsi_mat_p: np.ndarray


@functools.lru_cache(maxsize=None)
def make_fourstep_params(n1: int, n2: int, q: int | None = None,
                         bits: int = 30) -> FourStepParams:
    n = n1 * n2
    if q is None:
        from repro.core.params import gen_ntt_primes
        q = gen_ntt_primes(1, n, bits)[0]
    psi = root_of_unity(2 * n, q)
    omega = pow(psi, 2, q)
    p1 = make_ntt_params(n1, q=q, psi=pow(psi, n2, q))
    p2 = make_ntt_params(n2, q=q, psi=pow(psi, n1, q))

    def pow_table(base: int, rows: int, cols: int, row_stride_fn) -> np.ndarray:
        t = np.empty((rows, cols), dtype=np.uint32)
        for r in range(rows):
            e = row_stride_fn(r)
            step = pow(base, e, q)
            v = 1
            for c in range(cols):
                t[r, c] = v
                v = v * step % q
        return t

    # tw_mat[k1, j2] = omega^(j2*k1)
    tw_mat = pow_table(omega, n1, n2, lambda k1: k1)
    iomega = pow(omega, q - 2, q)
    itw_mat = pow_table(iomega, n1, n2, lambda k1: k1)
    # psi_mat[j1, j2] = psi^(j1*n2 + j2): row j1 starts at psi^(j1*n2), steps psi
    psi_mat = np.empty((n1, n2), dtype=np.uint32)
    ipsi_mat = np.empty((n1, n2), dtype=np.uint32)   # psi^-i only: sub-iNTTs
    ipsi = pow(psi, q - 2, q)                        # already contribute 1/n
    for j1 in range(n1):
        v = pow(psi, j1 * n2, q)
        w = pow(ipsi, j1 * n2, q)
        for j2 in range(n2):
            psi_mat[j1, j2] = v
            ipsi_mat[j1, j2] = w
            v = v * psi % q
            w = w * ipsi % q

    def sh(t):
        return np.vectorize(lambda w: shoup_precompute(int(w), q))(t).astype(np.uint32)

    return FourStepParams(n=n, n1=n1, n2=n2, q=q, p1=p1, p2=p2,
                          tw_mat=tw_mat, tw_mat_p=sh(tw_mat),
                          itw_mat=itw_mat, itw_mat_p=sh(itw_mat),
                          psi_mat=psi_mat, psi_mat_p=sh(psi_mat),
                          ipsi_mat=ipsi_mat, ipsi_mat_p=sh(ipsi_mat))


# --------------------------------------------------------------- local

@functools.lru_cache(maxsize=None)
def _banks_pack(n1: int, n2: int, q: int) -> dict:
    """Single-prime (k=1) FourStepPack for the banks pipeline."""
    from repro.fhe.batched import fourstep_pack_from_params
    return fourstep_pack_from_params([make_fourstep_params(n1, n2, q)])


def fourstep_ntt(a, fsp: FourStepParams, negacyclic: bool = False, *,
                 use_pallas: bool | None = None, tile: int = 8):
    """a: (..., n) u32 -> natural-order NTT via the four-step path.

    The functional model of the paper's Fig 21 schedule, dispatched to
    ``kernels.ops.ntt_fourstep_banks`` as a k=1 bank row (the one-kernel
    transform on TPU, the multi-pass reference elsewhere)."""
    fp = _banks_pack(fsp.n1, fsp.n2, fsp.q)
    return ops.ntt_fourstep_banks(jnp.asarray(a)[None], fp,
                                  negacyclic=negacyclic,
                                  use_pallas=use_pallas, tile=tile)[0]


def fourstep_intt(A, fsp: FourStepParams, negacyclic: bool = False, *,
                  use_pallas: bool | None = None, tile: int = 8):
    fp = _banks_pack(fsp.n1, fsp.n2, fsp.q)
    return ops.intt_fourstep_banks(jnp.asarray(A)[None], fp,
                                   negacyclic=negacyclic,
                                   use_pallas=use_pallas, tile=tile)[0]


def fourstep_schedule(n1: int, n2: int) -> dict:
    """Static structure of the §IX schedule — what runs in each pass.

    Used by tests to cross-validate ``srm_sim.large_ntt_cycles`` (the
    paper's analytic 2^14 model: two passes, each a batch of 128 NTT-128
    transforms through 128 units) against the actual four-step pipeline
    shape, and by the dry-run cells to size the reorder collective."""
    return {
        "passes": 2,
        # pass 1 runs one NTT-N1 per column, pass 2 one NTT-N2 per row
        "transforms_per_pass": (n2, n1),
        "transform_sizes": (n1, n2),
        "butterfly_cycles_per_pass": (n2 * (n1 // 2), n1 * (n2 // 2)),
        "reorders": 1,                  # the inter-pass transpose/all-to-all
        "twiddle_muls": n1 * n2,        # fused step-3 correction
    }


# ------------------------------------------------------------- sharded

def fourstep_ntt_sharded(a2d, fsp: FourStepParams, mesh, axis: str = "model",
                         negacyclic: bool = False):
    """Distributed four-step over one mesh axis.

    a2d: (n1, n2) matrix, sharded P(None, axis) (columns across chips).
    Output: D matrix (n1, n2) sharded P(axis, None); the caller reads
    A_hat[k2*n1+k1] = D[k1,k2].  The single all_to_all IS the paper's
    reorder network between the two NTT-128 banks.
    """
    q = jnp.uint32(fsp.q)
    tw1 = jnp.asarray(fsp.p1.tw)
    tw1p = jnp.asarray(fsp.p1.twp)
    perm1 = bitrev_perm(fsp.n1)             # involution: bitrev->natural

    def local(x, twm, twmp, psim, psimp):
        # x: (n1, n2/D) local block
        if negacyclic:
            x = mulmod_shoup(x, psim, psimp, q)
        xt = jnp.swapaxes(x, -1, -2)              # (n2loc, n1)
        xt = cg_ntt(xt, tw1, tw1p, fsp.q)[..., perm1]
        x = jnp.swapaxes(xt, -1, -2)
        x = mulmod_shoup(x, twm, twmp, q)
        # reorder network: (n1, n2loc) -> (n1/D, n2)
        x = jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=1, tiled=True)
        x = ntt_natural(x, fsp.p2)                # rows local now
        return x

    spec_cols = P(None, axis)
    spec_rows = P(axis, None)
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(spec_cols, spec_cols, spec_cols, spec_cols, spec_cols),
        out_specs=spec_rows)
    return fn(a2d, jnp.asarray(fsp.tw_mat), jnp.asarray(fsp.tw_mat_p),
              jnp.asarray(fsp.psi_mat), jnp.asarray(fsp.psi_mat_p))
