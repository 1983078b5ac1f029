"""Quickstart: the paper's pipeline end to end in 60 seconds.

  1. NTT-128 through the constant-geometry network (+ SRM cycle sim)
  2. negacyclic polynomial multiply via NTT (the FHE primitive)
  3. CKKS: encrypt two vectors, multiply homomorphically, decrypt
  4. the paper's headline numbers from the cycle model

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import srm_sim
from repro.core.ntt import ntt_negacyclic, intt_negacyclic, ntt_cyclic
from repro.core.params import make_ntt_params
from repro.core.modmath import mulmod_np
from repro.fhe.ckks import CkksContext


def main():
    rng = np.random.default_rng(0)

    # 1 — NTT-128 (paper §IV) --------------------------------------------
    p = make_ntt_params(128)
    poly = rng.integers(0, p.q, 128, dtype=np.uint32)
    A = ntt_cyclic(jnp.asarray(poly), p)
    print(f"NTT-128 over q={p.q}: in[:4]={poly[:4]} out[:4]={np.asarray(A)[:4]}")

    pipe = srm_sim.NTT128Pipeline(p)
    out, stats = pipe.run(poly[None, :])
    print(f"SRM pipeline simulator: match={np.array_equal(out[0], np.asarray(A))} "
          f"latency={stats['latency_cycles']} cycles (paper Table III: 1,036)")

    # 2 — negacyclic multiply (ring R_q = Z_q[x]/(x^n+1)) ------------------
    a = rng.integers(0, p.q, 128, dtype=np.uint32)
    b = rng.integers(0, p.q, 128, dtype=np.uint32)
    C = mulmod_np(np.asarray(ntt_negacyclic(jnp.asarray(a), p)),
                  np.asarray(ntt_negacyclic(jnp.asarray(b), p)), p.q)
    c = intt_negacyclic(jnp.asarray(C), p)
    print(f"poly multiply via NTT: c[:4]={np.asarray(c)[:4]}")

    # 3 — CKKS (paper §II/§VIII) ------------------------------------------
    ctx = CkksContext(n=512, levels=3, seed=1)
    z1 = rng.uniform(-1, 1, ctx.slots)
    z2 = rng.uniform(-1, 1, ctx.slots)
    ct = ctx.rescale(ctx.multiply(ctx.encrypt(ctx.encode(z1)),
                                  ctx.encrypt(ctx.encode(z2))))
    got = ctx.decrypt_decode(ct).real
    err = np.max(np.abs(got - z1 * z2))
    print(f"CKKS enc(x)*enc(y): max err {err:.2e} (scale 2^28)")

    # 4 — headline numbers (cycle model) -----------------------------------
    t3 = srm_sim.table3_model()
    big = srm_sim.large_ntt_cycles()
    ks = srm_sim.keyswitch_cycles()
    print(f"NTT-128 @34GHz: {t3['throughput_mntt_per_s']:.2f}M NTT/s (paper: 531)")
    print(f"2^14 NTT: {big['ideal_latency_ns']:.0f} ns (paper: ~482)")
    print(f"key-switch: {ks['throughput_per_s']:.2e}/s, "
          f"{ks['speedup_vs_cmos']:.0f}x HEAX (paper: 1.63M/s)")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
