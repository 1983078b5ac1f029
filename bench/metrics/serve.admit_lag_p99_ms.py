"""99th percentile over the window's requests of admitted - due: how
late the serve loop took a request in after it was due (the engine's
``serve.request`` events).  A host stall shows here first."""
from harness import arith, lifecycle


def read(obs):
    reqs = lifecycle.requests(obs["spans"])
    if not reqs:
        return None
    return arith.percentile([r["admitted"] - r["due"] for r in reqs], 99) / 1e3
