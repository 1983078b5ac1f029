"""The one traffic generator: turns a traffic file and a seed into work.

A traffic file (``bench/traffic/<mix>.json``) holds parameters only:

  loop        "open" (requests due on a schedule), "backlog" (chunks of
              requests all due at once, one after another) or "closed"
              (back-to-back batched calls)
  arrivals    open loop: {"process": "poisson" | "gamma", "rate": req/s,
              "shape": gamma shape}
  chunk       backlog: requests per chunk
  mix         op -> share of requests
  rotations   amounts a "rotate" request draws from, in equal numbers
  pool        distinct inputs each request draws from
  batch       closed loop: items per call
  check_sample  answers of the window compared with the reference

Every seed gets the same arrival times and the same multiset of ops and
rotation amounts: the seed deals the requests over the arrival times in
another order and picks the pooled input each reads, never how much work
a run holds or when its bursts come.  The arrival times are drawn once
from ``GAP_SEED``, scaled so that the last request is due exactly at the
end of the window.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# seed of the fixed inter-arrival gaps, the same for every run
GAP_SEED = 0


@dataclasses.dataclass
class Request:
    rid: int
    op: str
    pool: int                 # index of the pooled input it reads
    r: int = 0                # rotation amount ("rotate" only)


def _counts(mix: dict, n: int) -> dict:
    """Largest-remainder split of n requests over the mix's shares."""
    ops = sorted(mix)
    total = float(sum(mix.values()))
    raw = {op: mix[op] / total * n for op in ops}
    counts = {op: int(np.floor(raw[op])) for op in ops}
    left = n - sum(counts.values())
    for op in sorted(ops, key=lambda o: raw[o] - counts[o], reverse=True)[:left]:
        counts[op] += 1
    return counts


def requests(traffic: dict, n: int, rng: np.random.Generator,
             first_rid: int = 0) -> list[Request]:
    """n requests of the mix, in a seeded order."""
    counts = _counts(traffic["mix"], n)
    ops = np.array([op for op in sorted(counts) for _ in range(counts[op])])
    ops = ops[rng.permutation(n)]
    rots = traffic.get("rotations") or [0]
    n_rot = int(np.sum(ops == "rotate"))
    amounts = np.resize(np.array(rots, dtype=np.int64), n_rot)
    amounts = amounts[rng.permutation(n_rot)]
    pool = rng.integers(0, int(traffic.get("pool", 1)), n)
    out, k = [], 0
    for i, op in enumerate(ops):
        r = 0
        if op == "rotate":
            r = int(amounts[k])
            k += 1
        out.append(Request(first_rid + i, str(op), int(pool[i]), r))
    return out


def gaps(arrivals: dict, n: int, seconds: float):
    """The fixed n inter-arrival gaps, summing to seconds."""
    fixed = np.random.default_rng(GAP_SEED)
    rate = float(arrivals["rate"])
    if arrivals["process"] == "poisson":
        g = fixed.exponential(1.0 / rate, n)
    elif arrivals["process"] == "gamma":
        shape = float(arrivals["shape"])
        g = fixed.gamma(shape, 1.0 / (shape * rate), n)
    else:
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    return g * (seconds / g.sum())


def open_loop(traffic: dict, seed: int, seconds: float):
    """(requests, arrival seconds) of an open-loop window."""
    n = int(round(float(traffic["arrivals"]["rate"]) * seconds))
    if n < 1:
        raise ValueError("the window holds no request at this rate")
    reqs = requests(traffic, n, np.random.default_rng(seed))
    return reqs, np.cumsum(gaps(traffic["arrivals"], n, seconds))


def closed_batches(traffic: dict, seed: int):
    """Endless calls of ``batch`` pooled operand pairs."""
    rng = np.random.default_rng(seed)
    b, pool = int(traffic["batch"]), int(traffic["pool"])
    while True:
        yield rng.integers(0, pool, (b, 2))
