#!/usr/bin/env python3
"""Records the small profiler trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out.xplane.pb.gz>

On a TPU: a few batched relinearising multiplies at n=2^12 with three
limbs, inside a ``bench.window`` annotation, with one 50 ms host sleep
inside a ``bench.sleep`` span while the device has nothing queued.
"""
import gzip
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import jax  # noqa: E402

from harness import trace as tr  # noqa: E402


def main(out: str) -> int:
    if jax.default_backend() != "tpu":
        print("record_trace.py: needs a TPU", file=sys.stderr)
        return 2
    from repro import obs
    from repro.fhe.ckks import CkksContext

    ctx = CkksContext(n=1 << 12, levels=2, seed=1)
    plan = ctx.plan()
    cts = [ctx.encrypt(ctx.encode([0.5])) for _ in range(8)]
    jax.block_until_ready(plan.multiply_many(cts, cts)[0].c0.data)
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        obs.enable(forward_to_jax=True)
        with tr.capture(log_dir):
            with jax.profiler.TraceAnnotation(tr.WINDOW):
                for _ in range(3):
                    jax.block_until_ready(plan.multiply_many(cts, cts)[0].c0.data)
                with obs.span("bench.sleep"):
                    time.sleep(0.05)
                jax.block_until_ready(plan.multiply_many(cts, cts)[0].c0.data)
        obs.disable()
        with open(tr.find_xplane(log_dir), "rb") as f, \
                gzip.open(out, "wb") as g:
            g.write(f.read())
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    print(out, os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
