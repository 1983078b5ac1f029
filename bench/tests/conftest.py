"""Tests of the benchmark harness; they run on the CPU without a chip:

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


import pytest  # noqa: E402


def small(config: str, traffic: str, rate: float | None = None):
    """(bench, cell, config, traffic) of a configuration under a traffic
    mix, cut to a size the CPU runs in seconds: n=2^10 with 3 limbs, an
    8x8 matrix, tile 2, backlog chunks of 8."""
    from harness import spec

    bench = spec.load_benchmark()
    cell = {"name": f"{config}.{traffic}", "config": config,
            "traffic": traffic, "chips": 1}
    cfg = spec.load_config(config)
    if cfg["scheme"] == "ckks":
        cfg.update(n=1024, levels=2, ntt_order="bitrev", matrix=[8, 8])
    cfg.update(batch_tile=2, max_batch=4)
    tr = spec.load_traffic(traffic)
    if "arrivals" in tr:
        tr["arrivals"]["rate"] = rate or 40.0
    tr.update({"batch": 4} if tr["loop"] == "closed" else {})
    tr.update({"chunk": 8} if tr["loop"] == "backlog" else {})
    return bench, cell, cfg, tr


@pytest.fixture
def run_small():
    import run

    def go(cell, seed=2**31 + 5, seconds=1.0, traffic=None, config=None,
           **kw):
        bench, cell, cfg, tr = small(*cell)
        tr.update(traffic or {})
        cfg.update(config or {})
        return run.run_cell(bench, cell, seed, seconds, False, device=None,
                            config=cfg, traffic=tr, **kw)
    return go
