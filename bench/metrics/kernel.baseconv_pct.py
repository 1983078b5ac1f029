"""The base-conversion kernel's share of the device's busy time in the
traced window: the device time of its ops among the reduced trace's top
ops over busy time."""
from harness import baseconv


def read(obs):
    tr = obs["trace"]
    if not tr or not tr["busy_s"]:
        return None
    up_s, down = baseconv.visible(tr)
    seconds = up_s + sum(down)
    if not seconds:
        return None
    return 100.0 * seconds / tr["busy_s"]
