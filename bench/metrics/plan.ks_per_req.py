"""Key switches the plan ran per completed request (the plan's
``key_switches`` counter over the window, as the engine reports it)."""


def read(obs):
    win = obs["window"]
    eng = win.get("engine")
    if not eng or not win["completed"]:
        return None
    return eng["key_switches"] / win["completed"]
