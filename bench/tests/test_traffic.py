"""The generator gives every seed the same work and arrival times, in
another order."""
import collections

import numpy as np

from harness import traffic as gen

MIX = {"loop": "open", "arrivals": {"process": "poisson", "rate": 200.0},
       "mix": {"multiply": 0.3, "rotate": 0.35, "rescale": 0.2,
               "conjugate": 0.1, "matvec": 0.05},
       "rotations": [1, 2, 4, 8, 16, 32, 64], "pool": 16}


def _shape(reqs):
    return (collections.Counter(r.op for r in reqs),
            collections.Counter(r.r for r in reqs))


def test_same_multiset_for_every_seed():
    a, ta = gen.open_loop(MIX, 1, 10.0)
    b, tb = gen.open_loop(MIX, 2**31 + 17, 10.0)
    assert len(a) == len(b) == 2000
    assert _shape(a) == _shape(b)
    assert [r.op for r in a] != [r.op for r in b]
    assert np.array_equal(ta, tb)          # same arrival times, bursts too
    assert ta[-1] == tb[-1] == 10.0 or np.isclose(ta[-1], 10.0)
    counts = _shape(a)[0]
    assert counts["matvec"] == 100 and counts["rotate"] == 700
    assert set(_shape(a)[1]) == {0, 1, 2, 4, 8, 16, 32, 64}


def test_same_seed_same_work():
    a, ta = gen.open_loop(MIX, 7, 5.0)
    b, tb = gen.open_loop(MIX, 7, 5.0)
    assert a == b and np.array_equal(ta, tb)


def test_gamma_bursts():
    mix = dict(MIX, arrivals={"process": "gamma", "shape": 0.25, "rate": 100.0})
    _, t = gen.open_loop(mix, 3, 20.0)
    g = np.diff(t, prepend=0)
    assert abs(np.std(g) / np.mean(g) - 2.0) < 0.3          # CV ~ 2
