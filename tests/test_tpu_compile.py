"""Compile every main-path kernel family for a described TPU v5e chip.

Interpret mode (the CPU default) executes kernels that Mosaic may still
refuse: blocks off the (8, 128) tiling, vector ops the target lacks,
lane gathers.  These tests lower and compile each Pallas kernel of the
CKKS serve path and of ML-KEM at its real widths for one chip of a
described ``v5e:2x2`` topology — the TPU compiler is installed, no chip
is needed, and nothing runs.  The widths:

  * banks fwd/inv NTT at the four-step pass shape of the 2^14 ring
    (n = 128 = ``fourstep_split(2^14)[0]``), 8 RNS limbs, batch 128*B,
    and at the key switch's digit-extended passes (9 primes, 32,768 and
    8,192 rows), whose blocks span many vregs;
  * ``twiddle_mul_banks`` on the 2^14 ring, 8 limbs;
  * ``dyadic_inner_banks``: 8 digits over 9 primes (8 limbs + the
    special prime), shared-key and per-batch-key layouts;
  * the Galois automorphism at 2^14 (one XLA gather, no kernel);
  * ML-KEM's u16 ring (n=256, q=3329) NTT/iNTT and basemul, computing
    in u32 lanes;
  * the grouped-digit key switch of the 2^15 ring (21 limbs, 3 digits of
    7 primes, 7 special primes, B = 32): the base-conversion kernel's
    mod-up (7 primes to 28, three digits) and mod-down (7 to 21, both
    halves), and the four-step passes of its 256 x 128 split over the
    28-prime digit extensions.

The topology is described inside a module fixture (never at import
time) and the persistent compilation cache is off around the compiles:
a compile for a described chip cannot be read back without one.

Run alone with ``JAX_PLATFORMS=cpu python -m pytest tests/test_tpu_compile.py``.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.params import fourstep_split
from repro.core.ringspec import MLKEM_RING, ring_table_pack
from repro.kernels import base_conv_kernel, dyadic_kernel, ntt_kernel, ops

N_RING = 1 << 14
N_PASS = fourstep_split(N_RING)[0]          # 128
LIMBS = 8                                   # L+1 (paper Fig 22)
B = 32                                      # ciphertexts per dispatch
TILE = 8
U32 = jnp.uint32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=U32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    return fn.lower(*args, **static).compile()


def _banks_tables(s, k, n, stages):
    return dict(
        qs=_sds(s, (k,)), tw=_sds(s, (k, stages, n // 2)),
        twp=_sds(s, (k, stages, n // 2)), w=_sds(s, (k, n)),
        ninv=_sds(s, (k,)))


def _compile_pass(s, k, rows, inverse, n=N_PASS):
    """Compile one lazy n-point banks NTT pass over (k, rows, n)."""
    stages = n.bit_length() - 1
    t = _banks_tables(s, k, n, stages)
    x = _sds(s, (k, rows, n))
    common = dict(stages=stages, negacyclic=False, tile=TILE, lazy=True,
                  reduce_out=False, interpret=False)
    if inverse:
        return _compile(ntt_kernel.ntt_inv_banks_pallas, x, t["qs"],
                        t["ninv"], t["ninv"], t["tw"], t["twp"], t["w"],
                        t["w"], **common)
    return _compile(ntt_kernel.ntt_fwd_banks_pallas, x, t["qs"], t["tw"],
                    t["twp"], t["w"], t["w"], **common)


@pytest.mark.parametrize("inverse", [False, True], ids=["fwd", "inv"])
def test_ntt_banks_fourstep_pass(one_chip, inverse):
    c = _compile_pass(one_chip, LIMBS, N_PASS * B, inverse)
    assert "tpu_custom_call" in c.as_text()


# (primes, rows) of the four-step passes in the key switch: 9 primes
# (8 limbs + the special prime) over 8 digits x B ciphertexts x 128
# columns, at the key-switch batch (B = 32) and the serve tile (B = 8).
PASS_GEOMETRY = {"keyswitch_b32": (LIMBS + 1, LIMBS * 32 * N_PASS),
                 "serve_b8": (LIMBS + 1, LIMBS * 8 * N_PASS)}


@pytest.mark.parametrize("inverse", [False, True], ids=["fwd", "inv"])
@pytest.mark.parametrize("shape", list(PASS_GEOMETRY))
def test_ntt_banks_pass_block_geometry(one_chip, shape, inverse):
    """The many-row blocks the banks NTT takes from these shapes fit
    the chip's scoped VMEM (the compile refuses a block that does not)."""
    k, rows = PASS_GEOMETRY[shape]
    assert ntt_kernel.block_rows(rows, N_PASS, TILE) > TILE
    c = _compile_pass(one_chip, k, rows, inverse)
    assert "tpu_custom_call" in c.as_text()


def test_twiddle_mul_banks_2_14(one_chip):
    s, k, n = one_chip, LIMBS, N_RING
    c = _compile(ntt_kernel.twiddle_mul_banks_pallas, _sds(s, (k, B, n)),
                 _sds(s, (k,)), _sds(s, (k, n)), _sds(s, (k, n)),
                 tile=TILE, lazy=True, interpret=False)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("per_batch", [False, True],
                         ids=["shared_key", "per_batch_key"])
def test_dyadic_inner_banks_keyswitch(one_chip, per_batch):
    s, d, kp1, n = one_chip, LIMBS, LIMBS + 1, N_RING
    evk = (d, kp1, B, n) if per_batch else (d, kp1, n)
    c = _compile(dyadic_kernel.dyadic_inner_banks, _sds(s, (d, kp1, B, n)),
                 _sds(s, evk), _sds(s, (kp1,)), _sds(s, (kp1,)),
                 digits=d, tile=TILE, lazy=True, interpret=False)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("layout", ["shared_row", "per_batch_rows",
                                    "digits_shared"])
def test_galois_gather_2_14(one_chip, layout):
    s, k, n = one_chip, LIMBS, N_RING
    if layout == "shared_row":
        fn = jax.jit(ops.galois_banks)
        args = (_sds(s, (k, B, n)), _sds(s, (n,), jnp.int32))
    elif layout == "per_batch_rows":
        fn = jax.jit(ops.galois_banks)
        args = (_sds(s, (k, B, n)), _sds(s, (B, n), jnp.int32))
    else:
        fn = jax.jit(ops.galois_digits_banks)
        args = (_sds(s, (LIMBS, k + 1, 1, n)), _sds(s, (B, n), jnp.int32))
    c = fn.lower(*args).compile()
    assert "tpu_custom_call" not in c.as_text()     # plain XLA gather


def _mlkem_tables(s):
    t = ring_table_pack(MLKEM_RING)
    return {name: _sds(s, np.shape(v), np.asarray(v).dtype)
            for name, v in t.items()}


@pytest.mark.parametrize("inverse", [False, True], ids=["fwd", "inv"])
def test_mlkem_ntt_u16_ring(one_chip, inverse):
    s, n = one_chip, MLKEM_RING.n
    t = _mlkem_tables(s)
    x = _sds(s, (1, 64 * 3, n))          # b=64 requests x K=3 rows, u32
    common = dict(stages=MLKEM_RING.stages, negacyclic=False, tile=TILE,
                  lazy=True, bits=16, interpret=False)
    if inverse:
        c = _compile(ntt_kernel.ntt_inv_banks_pallas, x, t["qs"], t["ninv"],
                     t["ninv_p"], t["itw"], t["itwp"], t["ipsin"],
                     t["ipsinp"], **common)
    else:
        c = _compile(ntt_kernel.ntt_fwd_banks_pallas, x, t["qs"], t["tw"],
                     t["twp"], t["psi"], t["psip"], **common)
    assert "tpu_custom_call" in c.as_text()


def test_mlkem_basemul_u16_ring(one_chip):
    s, n = one_chip, MLKEM_RING.n
    t = _mlkem_tables(s)
    x = _sds(s, (1, 64 * 9, n))          # b=64 requests x K*K products
    c = _compile(dyadic_kernel.dyadic_basemul_banks, x, x, t["qs"], t["mu"],
                 t["gamma"], t["gammap"], tile=TILE, lazy=True, bits=16,
                 interpret=False)
    assert "tpu_custom_call" in c.as_text()


# The grouped-digit key switch at N = 2^15: 21 limbs in 3 digits of 7
# primes, 7 special primes, B = 32 ciphertexts.
N15, K15, ALPHA15, DIGITS15 = 1 << 15, 21, 7, 3


@pytest.mark.parametrize("d,targets,b", [(DIGITS15, K15 + ALPHA15, B),
                                         (1, K15, 2 * B)],
                         ids=["mod_up", "mod_down"])
def test_base_conv_banks_2_15(one_chip, d, targets, b):
    s, m = one_chip, ALPHA15
    c = _compile(base_conv_kernel.base_conv_banks_pallas,
                 _sds(s, (d, m, b, N15)), _sds(s, (d, m)), _sds(s, (d, m)),
                 _sds(s, (d, m)), _sds(s, (targets,)),
                 _sds(s, (d, m, targets)), _sds(s, (d, m, targets)),
                 _sds(s, (d, m, targets)), tile=TILE,
                 name=base_conv_kernel.MODUP, interpret=False)
    assert "tpu_custom_call" in c.as_text()
    assert base_conv_kernel.MODUP in c.as_text()


@pytest.mark.parametrize("inverse", [False, True], ids=["fwd", "inv"])
@pytest.mark.parametrize("n", list(fourstep_split(N15)), ids=["n1", "n2"])
def test_ntt_banks_fourstep_pass_2_15(one_chip, n, inverse):
    """The 256 x 128 split of the 2^15 ring over the key switch's digit
    extensions: 28 primes, 3 digits x B ciphertexts x the other factor."""
    assert fourstep_split(N15) == (256, 128)
    rows = DIGITS15 * B * (N15 // n)
    c = _compile_pass(one_chip, K15 + ALPHA15, rows, inverse, n=n)
    assert "tpu_custom_call" in c.as_text()
