"""Requests per engine dispatch (engine counters ``batched_ops`` over
``dispatches``, summed over the window's drains)."""


def read(obs):
    eng = obs["window"].get("engine")
    if not eng or not eng.get("dispatches"):
        return None
    return eng["batched_ops"] / eng["dispatches"]
