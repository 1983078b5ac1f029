"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window, from the profiler trace."""


def read(obs):
    tr = obs["trace"]
    if not tr or not tr["window_s"] or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
