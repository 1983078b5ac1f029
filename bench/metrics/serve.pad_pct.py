"""Share of dispatched batch rows that are tile padding (engine counters
``padded`` over ``batched_ops + padded``)."""


def read(obs):
    eng = obs["window"].get("engine")
    if not eng or not eng.get("batched_ops"):
        return None
    return 100.0 * eng["padded"] / (eng["batched_ops"] + eng["padded"])
