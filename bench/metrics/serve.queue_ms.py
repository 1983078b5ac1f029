"""Median over dispatched requests of dispatched - admitted: the wait
for its group's turn plus the host's grouping and stacking, up to the
return of its group's dispatch (the engine's ``serve.request``
events)."""
from harness import arith, lifecycle


def read(obs):
    reqs = lifecycle.dispatched(obs["spans"])
    if not reqs:
        return None
    return arith.percentile([r["dispatched"] - r["admitted"] for r in reqs],
                            50) / 1e3
