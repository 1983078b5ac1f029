"""Pallas dispatch policy: interpret-mode resolution lives in ONE shared
helper (``kernels.resolve_interpret``), and every ``ops.py`` pallas path
resolves to COMPILED mode when the backend reports TPU — the regression
here was kernel entry points defaulting ``interpret=True``, so any call
site that forgot to thread ``interpret=not _on_tpu()`` silently ran the
interpreter on TPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro import kernels
from repro.core.params import galois_eval_perm, gen_ntt_primes, make_ntt_params
from repro.core.ringspec import MLKEM_RING, ring_table_pack
from repro.fhe import batched as FB
from repro.kernels import ops

RNG = np.random.default_rng(211)


def test_resolve_interpret_explicit_flag_wins(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernels.resolve_interpret(True) is True
    assert kernels.resolve_interpret(False) is False


def test_resolve_interpret_backend_default(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernels.resolve_interpret(None) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert kernels.resolve_interpret(None) is True


class _Captured(Exception):
    """Raised by the pallas_call stub so no kernel actually lowers for a
    backend this container doesn't have."""


def test_all_ops_pallas_paths_compile_on_tpu(monkeypatch):
    """Drive EVERY ops.py pallas entry point with the backend patched to
    report TPU and NO interpret flag threaded anywhere, intercepting
    ``pl.pallas_call``: each path must resolve interpret=False (compiled
    Mosaic), including via the ``use_pallas=None`` default."""
    seen = []

    def fake_pallas_call(kernel, **kw):
        def runner(*args):
            seen.append(kw.get("interpret", "missing"))
            raise _Captured()
        return runner

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pl, "pallas_call", fake_pallas_call)
    jax.clear_caches()      # force retrace of the jitted kernel wrappers

    n, k = 64, 2
    p = make_ntt_params(n)
    primes = gen_ntt_primes(k, n, bits=30)
    t = FB.build_table_pack(primes, n)
    x1 = jnp.asarray(RNG.integers(0, p.q, (8, n), dtype=np.uint32))
    xk = jnp.asarray(np.stack([RNG.integers(0, q, (4, n), dtype=np.uint32)
                               for q in primes]))
    idx = jnp.asarray(galois_eval_perm(5, n, False), jnp.int32)
    idx2 = jnp.stack([idx] * 4)
    rp = ring_table_pack(MLKEM_RING)
    xr = jnp.asarray(RNG.integers(0, MLKEM_RING.q, (1, 4, MLKEM_RING.n),
                                  dtype=np.uint32))
    ext = jnp.asarray(np.stack([np.asarray(xk)] * k))        # (d, k, 4, n)
    evk3 = jnp.asarray(np.stack([np.asarray(xk)[:, 0]] * k))  # (d, k, n)
    fp = FB.build_fourstep_pack(primes, n)

    calls = [
        lambda: ops.ntt(x1, p),
        lambda: ops.intt(x1, p),
        lambda: ops.dyadic_mul(x1, x1, p),
        lambda: ops.dyadic_mac(x1, x1, x1, p),
        lambda: ops.ntt_banks(xk, t),
        lambda: ops.intt_banks(xk, t),
        lambda: ops.ntt_fourstep_banks(xk, fp),
        lambda: ops.intt_fourstep_banks(xk, fp),
        lambda: ops.dyadic_inner_banks(ext, evk3, t),
        lambda: ops.dyadic_inner_banks(ext, ext, t),      # per-batch evk
        lambda: ops.ntt_banks(xr, rp, negacyclic=False),  # u16 ring
        lambda: ops.intt_banks(xr, rp, negacyclic=False),
        lambda: ops.dyadic_basemul_banks(xr, xr, rp),
    ]
    # the Galois automorphism is one XLA gather on every backend: it
    # must run to completion without reaching any Pallas kernel
    gathers = [
        lambda: ops.galois_banks(xk, idx),
        lambda: ops.galois_banks(xk, idx2),               # per-batch rows
        lambda: ops.galois_digits_banks(ext, idx2),       # hoisted gather
        lambda: ops.galois_digits_banks(ext[:, :, :1], idx2),  # shared mode
    ]
    for call in calls:
        with pytest.raises(_Captured):
            call()
    for call in gathers:
        jax.block_until_ready(call())
    jax.clear_caches()      # drop the poisoned traces before other tests
    assert len(seen) == len(calls)
    assert all(v is False for v in seen), seen
