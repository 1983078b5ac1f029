"""99th percentile of request latency over every request of the window,
from its due time to the moment the harness saw its answer ready (the
harness's own stamps; an unanswered request counts as infinite)."""
from harness import arith


def read(obs):
    lat = obs["window"].get("latencies_s")
    if not lat:
        return None
    return arith.percentile(lat, 99) * 1e3
