"""The paper's §IX scale-out, both software forms:

1. the *local* large-N path — a 2^14-point NTT over an RNS basis
   composed from 128x128 four-step passes on the fused multi-prime
   banks kernels (``kernels.ops.ntt_fourstep_banks``; the same dispatch
   ``RnsPoly``/key-switch use for every ring with N >= 2^13), and
2. the *sharded* path — the scheme-level scale-out through
   ``EvalPlan(mesh=...)``: the batch axis of a 2^10-ring ciphertext
   multiply sharded over 1/2/4/8 (simulated) devices, each count
   verified bit-exact against the single-device program and reported
   as a scaling table (devices / wall / throughput / speedup /
   efficiency — the ntt-aie ``plot_efficiency`` report shape).

This is the same code path the sce-ntt/fourstep_16k dry-run cell lowers
for the 256/512-chip production meshes; the mesh convention ("b" shards
the ciphertext batch via collective-free shard_map twins, tables/keys
replicated) is documented in the README's Scale-out section.

Run:  PYTHONPATH=src python examples/distributed_ntt.py
(sets XLA_FLAGS itself — run as a fresh process)
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import compat
from repro.core import fourstep as fs
from repro.core.params import fourstep_split, gen_ntt_primes
from repro.fhe import batched as FB
from repro.kernels import ops


def demo_large_n_banks():
    n, k = 1 << 14, 2
    n1, n2 = fourstep_split(n)
    primes = gen_ntt_primes(k, n, bits=30)
    fp = FB.build_fourstep_pack(primes, n)
    rng = np.random.default_rng(0)
    x = jnp.asarray(np.stack([rng.integers(0, q, n, dtype=np.uint32)
                              for q in primes]))
    y = ops.ntt_fourstep_banks(x, fp)          # 2 banks passes + twiddle kernel
    back = np.asarray(ops.intt_fourstep_banks(y, fp))
    ok = np.array_equal(back, np.asarray(x))
    print(f"large-N banks four-step: n={n} = {n1}x{n2}, k={k} primes -> "
          f"roundtrip {'MATCH' if ok else 'MISMATCH'}")
    sched = fs.fourstep_schedule(n1, n2)
    print(f"  schedule: {sched['passes']} passes of "
          f"{sched['transforms_per_pass'][0]} NTT-{sched['transform_sizes'][0]} "
          f"unit transforms + 1 reorder (paper §IX: ~482 ns at 34 GHz)")
    assert ok


def demo_sharded_evalplan():
    """Batch-sharded CKKS multiply through ``EvalPlan(mesh=d x "b")``
    per device count — the software analog of the paper's replicated-PE
    throughput scaling, reported in the ntt-aie efficiency-table shape."""
    from repro.fhe.ckks import CkksContext
    from repro.fhe.evalplan import EvalPlan

    ctx = CkksContext(n=1024, levels=2, seed=23)
    B = 16
    rng = np.random.default_rng(5)

    def enc():
        z = rng.uniform(-1, 1, ctx.slots) + 1j * rng.uniform(-1, 1, ctx.slots)
        return ctx.encrypt(ctx.encode(z))

    cts = [enc() for _ in range(B)]
    bts = [enc() for _ in range(B)]
    avail = len(jax.devices())
    counts = [d for d in (1, 2, 4, 8) if d <= avail]

    def run(plan):
        out = plan.multiply_many(cts, bts)
        jax.block_until_ready([x.c0.data for x in out] +
                              [x.c1.data for x in out])
        return out

    print(f"sharded EvalPlan ckks multiply: n={ctx.n} B={B} over "
          f"{avail} simulated devices (mesh axis 'b')")
    print(f"{'devices':>8} {'time_us':>10} {'mul/s':>8} "
          f"{'speedup':>8} {'efficiency':>11} {'exact':>6}")
    ref, t1 = None, None
    for d in counts:
        plan = (ctx.plan() if d == 1 else EvalPlan(
            ctx, mesh=compat.make_mesh((d,), ("b",),
                                       devices=jax.devices()[:d])))
        out = run(plan)                              # compile + warm
        if ref is None:
            ref = out
        ok = all(
            np.array_equal(np.asarray(a.c0.data), np.asarray(b.c0.data))
            and np.array_equal(np.asarray(a.c1.data), np.asarray(b.c1.data))
            for a, b in zip(ref, out))
        t0 = time.perf_counter()
        for _ in range(3):
            run(plan)
        us = (time.perf_counter() - t0) / 3 * 1e6
        if t1 is None:
            t1 = us
        print(f"{d:>8} {us:>10.0f} {B / (us / 1e6):>8.0f} "
              f"x{t1 / us:>7.2f} {t1 / (us * d) * 100:>10.0f}% "
              f"{'OK' if ok else 'FAIL':>6}")
        assert ok
    print("(simulated host devices time-share the physical cores: "
          "speedup is real only when the host has the cores to back them)")


def main():
    demo_large_n_banks()
    demo_sharded_evalplan()


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
