"""Share of the HBM roofline that the batched relinearising multiplies
reach: the least bytes they must move (inputs, outputs, one relin key
per call; ``harness.arith.relin_multiply_bytes``) over device busy time
times the chip's HBM bandwidth.  v5e publishes no integer VPU peak, so
the bytes bound is the only roofline side that can be stated."""
from harness import arith


def read(obs):
    win, tr, peaks = obs["window"], obs["trace"], obs["peaks"]
    if not win.get("calls") or not tr or not tr["busy_s"] or not peaks:
        return None
    cfg = obs["config"]
    k = cfg["levels"] + 1
    moved = win["calls"] * arith.relin_multiply_bytes(
        cfg["n"], k, int(obs["traffic"]["batch"]), dnum=cfg.get("dnum"),
        alpha=cfg.get("special_primes", 1))
    return 100.0 * moved / (tr["busy_s"] * peaks["hbm_bytes_per_s"])
