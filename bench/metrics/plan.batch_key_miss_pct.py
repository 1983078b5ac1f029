"""Share of the plan's stacked galois-key lookups that missed its LRU
and restacked a key pattern (``plan.batch_key`` spans, ``hit``)."""


def read(obs):
    looks = [s["args"]["hit"] for s in obs["spans"]
             if s["name"] == "plan.batch_key"]
    if not looks:
        return None
    return 100.0 * looks.count(False) / len(looks)
