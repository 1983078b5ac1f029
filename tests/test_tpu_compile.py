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
  * the one-kernel four-step transform (forward and inverse) at the
    cells' shapes (2^14 over 9 primes, 2^15 over 28 and 21), and the
    2^14 batched key switch around it, whose XLA ops outside the
    kernels must stay within a byte budget;
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


# (ring, primes, polynomials per prime) of the one-kernel four-step
# transform in the cells: the 2^14 key switch's digit extensions (9
# primes x 8 digits x B = 32) and a serve dispatch (B = 8); the 2^15
# key switch's mod-up extension (28 primes x 3 digits x B) and its
# mod-down over the 21 limbs.
FOURSTEP_GEOMETRY = {"keyswitch_2_14": (N_RING, LIMBS + 1, LIMBS * B),
                     "serve_2_14": (N_RING, LIMBS + 1, LIMBS * 8),
                     "modup_2_15": (1 << 15, 28, 3 * B),
                     "moddown_2_15": (1 << 15, 21, B)}


def _fourstep_args(s, n, k, b, inverse):
    n1, n2 = fourstep_split(n)
    t = lambda *shape: _sds(s, (k,) + shape)
    rows_a, rows_c = t(n1 + 16, n2), t(n2 + 16, n1)
    w = ntt_kernel.ref_lanes(n1 if inverse else n2)
    x = _sds(s, (k, b, n // w, w))
    if inverse:
        return (x, t(), t(), t(), t(n1, n2), t(n1, n2), rows_c, rows_c,
                t(n1, n2), t(n1, n2), rows_a, rows_a)
    return (x, t(), t(n1, n2), t(n1, n2), rows_a, rows_a, t(n1, n2),
            t(n1, n2), rows_c, rows_c)


@pytest.mark.parametrize("inverse", [False, True], ids=["fwd", "inv"])
@pytest.mark.parametrize("shape", list(FOURSTEP_GEOMETRY))
def test_fourstep_kernel_cell_shapes(one_chip, shape, inverse):
    """The one-kernel four-step transform at the cells' shapes: its
    blocks of whole polynomials and its tables fit the chip's scoped
    VMEM (the compile refuses a kernel that does not)."""
    n, k, b = FOURSTEP_GEOMETRY[shape]
    fn = (ntt_kernel.fourstep_inv_banks_pallas if inverse
          else ntt_kernel.fourstep_fwd_banks_pallas)
    c = _compile(fn, *_fourstep_args(one_chip, n, k, b, inverse),
                 negacyclic=True, lazy=True, interpret=False)
    assert "tpu_custom_call" in c.as_text()


def _entry_writes(hlo: str, min_elems: int = 1 << 20) -> list[tuple]:
    """(name, opcode, bytes) of the ENTRY ops of an optimised HLO module
    that are not custom calls and whose output holds an array of at
    least ``min_elems`` elements; an async op counts once (its -done)."""
    import re
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}\n")]
    width = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2}
    out = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if not m:
            continue
        name, shape, op = m.groups()
        if op in ("parameter", "get-tuple-element", "tuple", "bitcast",
                  "constant", "custom-call") or op.endswith("-start"):
            continue
        arrays = [(dt, int(np.prod([int(d) for d in dims.split(",") if d])))
                  for dt, dims in re.findall(r"([a-z]+\d*)\[([\d,]*)\]",
                                             shape)]
        if arrays and max(e for _, e in arrays) >= min_elems:
            out.append((name, op, sum(e * width.get(dt, 4)
                                      for dt, e in arrays)))
    return out


def test_keyswitch_2_14_layout_traffic(one_chip, monkeypatch):
    """The batched key switch at 2^14 (8 limbs + 1 special prime,
    B = 32) keeps its four-step transforms in their kernels: the XLA ops
    outside the kernels (copies, transposing fusions, re-tiling
    reshapes, the mod-up and the key slices) write at most 0.75 GB a
    call, where the multi-pass pipeline's layout ops wrote ~3 GB."""
    from repro.core.params import gen_ntt_primes
    from repro.fhe import batched as FB
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    full = gen_ntt_primes(LIMBS + 1, N_RING, bits=30)
    sds = lambda a: _sds(one_chip, np.shape(a), np.asarray(a).dtype)
    t = jax.tree_util.tree_map(sds, FB.build_scalar_pack(list(full)))
    fsp = jax.tree_util.tree_map(sds, FB.build_fourstep_pack(list(full),
                                                             N_RING))
    key = _sds(one_chip, (LIMBS, LIMBS + 1, N_RING))
    fn = jax.jit(lambda d, eb, ea, t, f: FB.batched_keyswitch(
        d, eb, ea, t, fsp=f, tile=TILE))
    hlo = fn.lower(_sds(one_chip, (LIMBS, B, N_RING)), key, key, t,
                   fsp).compile().as_text()
    assert "fourstep_fwd_banks_pallas" in hlo
    assert "fourstep_inv_banks_pallas" in hlo
    assert "twiddle_mul" not in hlo and "ntt_fwd_banks" not in hlo
    written = sum(nbytes for _, _, nbytes in _entry_writes(hlo))
    assert written <= 0.75e9, _entry_writes(hlo)


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
