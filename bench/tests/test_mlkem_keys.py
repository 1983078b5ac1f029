"""The client keys the ML-KEM cell draws are valid FIPS 203 keys."""
import numpy as np

from harness.mlkem import encaps_keys
from reference import mlkem_spec as spec


def test_encaps_keys_decode_below_q_and_round_trip():
    ek = encaps_keys(np.random.default_rng(9), 8)
    assert ek.shape == (8, 1184) and ek.dtype == np.uint8
    for row in ek:
        b = bytes(row)
        polys = [spec.byte_decode(12, b[384 * i:384 * (i + 1)])
                 for i in range(spec.K)]
        assert all(c < spec.Q for p in polys for c in p)
        assert b"".join(spec.byte_encode(12, p) for p in polys) == b[:1152]
