"""ML-KEM-768 deployments: the server side of a TLS 1.3 hybrid handshake,
one encapsulation per handshake, through the serve engine; checked byte
for byte against ``reference/mlkem_spec.py``.

Configuration keys: ``parameter_set``, ``batch_tile`` and ``max_batch``
(the engine's), ``engine_ring`` ({"n", "levels"}: the small CKKS plan the
engine is built over; no CKKS request reaches it).  The client keys are
random valid FIPS 203 encapsulation keys made from the seed: 12-bit
coefficients below q, ByteEncode12, then a 32-byte rho.
"""
from __future__ import annotations

import numpy as np

from harness import serving, traffic as gen
from reference import mlkem_spec as spec


def encaps_keys(rng: np.random.Generator, count: int) -> np.ndarray:
    """(count, 1184) u8 valid ML-KEM-768 encapsulation keys."""
    t = rng.integers(0, spec.Q, (count, spec.K * spec.N // 2, 2),
                     dtype=np.uint32)
    lo, hi = t[..., 0], t[..., 1]
    packed = np.stack([lo & 0xFF, (lo >> 8) | ((hi & 0xF) << 4), hi >> 4],
                      axis=-1).astype(np.uint8).reshape(count, 384 * spec.K)
    rho = rng.integers(0, 256, (count, 32), dtype=np.uint8)
    return np.concatenate([packed, rho], axis=1)


class MlkemCell:
    """ML-KEM-768 encapsulation under one traffic mix."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.fhe.ckks import CkksContext

        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.loop = traffic["loop"]
        if config["parameter_set"] != "ML-KEM-768":
            raise ValueError(f"unsupported {config['parameter_set']!r}")
        rng = np.random.default_rng([seed, 1])
        pool = int(traffic["pool"])
        self.ek = encaps_keys(rng, pool)
        ring = config["engine_ring"]
        plan = CkksContext(n=ring["n"], levels=ring["levels"], seed=seed).plan()
        self.engine = serving.TimedEngine(plan, batch_tile=config["batch_tile"],
                                          max_batch=config["max_batch"])
        self._m_rng = np.random.default_rng([seed, 2])
        self.m: dict[int, np.ndarray] = {}
        self._warm()

    def _warm(self):
        from repro.pq import mlkem

        tile, top = self.cfg["batch_tile"], self.cfg["max_batch"]
        for b in range(tile, top + 1, tile):
            idx = np.arange(b) % len(self.ek)
            mlkem.encaps_batch(self.ek[idx], np.zeros((b, 32), np.uint8))

    def fhe_request(self, req: gen.Request):
        from repro.fhe.serve import FheRequest

        if req.op != "mlkem_encaps":
            raise ValueError(f"unknown ML-KEM op {req.op!r}")
        m = self._m_rng.integers(0, 256, 32, dtype=np.uint8)
        self.m[req.rid] = m
        return FheRequest(req.rid, "mlkem_encaps",
                          payload={"ek": self.ek[req.pool], "m": m})

    def check(self, items, control: bool = False):
        """items: [(op, pool, rid, answer)].  Returns (checked,
        {name: (value, limit)}); with ``control`` the spec's floored
        Compress stands in for the program."""
        mismatched = 0
        for _, pool, rid, ans in items:
            ek, m = bytes(self.ek[pool]), bytes(self.m[rid])
            want = spec.encaps(ek, m)
            got = (spec.encaps(ek, m, rounding=False) if control
                   else (bytes(ans[0]), bytes(ans[1])))
            mismatched += got != want
        return len(items), {"answers_mismatched": (mismatched, 0)}
