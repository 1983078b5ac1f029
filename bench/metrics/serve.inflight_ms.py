"""Median over dispatched requests of ready - dispatched: its batch on
the device, behind the batch in flight before it, until the drain
blocked on it (the engine's ``serve.request`` events)."""
from harness import arith, lifecycle


def read(obs):
    reqs = lifecycle.dispatched(obs["spans"])
    if not reqs:
        return None
    return arith.percentile([r["ready"] - r["dispatched"] for r in reqs],
                            50) / 1e3
