"""Modular multiplications per nanosecond of device busy time that the
key switches of the window need by their shapes
(``harness.arith.keyswitch_mulmods`` per key switch)."""
from harness import arith


def read(obs):
    win, tr = obs["window"], obs["trace"]
    if not win.get("calls") or not tr or not tr["busy_s"]:
        return None
    cfg = obs["config"]
    per_ks = arith.keyswitch_mulmods(cfg["n"], cfg["levels"] + 1,
                                     cfg.get("dnum"),
                                     cfg.get("special_primes", 1))
    return win["plan"]["key_switches"] * per_ks / (tr["busy_s"] * 1e9)
