"""Host microseconds per handshake spent in ``mlkem.encaps_batch`` outside
its kernel calls: each span's duration minus the ``ops.*`` spans nested
in it on the same thread, summed, over completed requests."""


def read(obs):
    spans = obs["spans"]
    done = obs["window"]["completed"]
    outer = [s for s in spans if s["name"] == "mlkem.encaps_batch"]
    if not outer or not done:
        return None
    total = 0.0
    for s in outer:
        end = s["ts_us"] + s["dur_us"]
        inner = sum(c["dur_us"] for c in spans
                    if c["name"].startswith("ops.") and c["tid"] == s["tid"]
                    and c["depth"] == s["depth"] + 1
                    and s["ts_us"] <= c["ts_us"] and c["ts_us"] + c["dur_us"] <= end)
        total += s["dur_us"] - inner
    return total / done
