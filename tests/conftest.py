"""Shared test plumbing.

Puts ``src/`` and ``tests/`` on sys.path so the suite runs with a bare
``python -m pytest`` (no PYTHONPATH needed), which also lets test
modules import the ``hypcompat`` optional-hypothesis shim directly
(and this module's helpers via ``from conftest import ...``).
"""
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (_HERE, os.path.join(os.path.dirname(_HERE), "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402  (after the path bootstrap)

try:
    from hypothesis import settings as _hyp_settings
except ImportError:       # hypcompat's deterministic sweep has no deadline
    pass
else:
    # one profile for every property test: the first example of a sweep
    # pays a JIT compile, which no per-example deadline can budget for
    _hyp_settings.register_profile("repro", deadline=None)
    _hyp_settings.load_profile("repro")


def ct_equal(a, b) -> bool:
    """Full ciphertext equality: both residue stacks bit-identical AND
    the host-side bookkeeping (scale, basis) matches — the pin the
    batched-vs-loop and engine-vs-single tests share."""
    return (np.array_equal(np.asarray(a.c0.data), np.asarray(b.c0.data))
            and np.array_equal(np.asarray(a.c1.data), np.asarray(b.c1.data))
            and a.scale == b.scale and a.primes == b.primes)
