"""Share of the HBM roofline that the window's base conversions reach:
the least bytes they must move (``harness.baseconv.conversion_bytes`` per
key switch, from the configuration's shapes, times the window's key
switches) over the device time of the conversion kernel's ops times the
chip's HBM bandwidth.  Only conversions whose ops are among the reduced
trace's top ops count, on both sides (``baseconv.visible_bytes``)."""
from harness import baseconv


def read(obs):
    win, tr, peaks = obs["window"], obs["trace"], obs["peaks"]
    ks = (win.get("plan") or {}).get("key_switches")
    if not tr or not peaks or not ks:
        return None
    up_s, down = baseconv.visible(tr)
    seconds = up_s + sum(down)
    moved = baseconv.visible_bytes(obs["config"], ks, tr)
    if not seconds or not moved:
        return None
    return 100.0 * moved / (seconds * peaks["hbm_bytes_per_s"])
