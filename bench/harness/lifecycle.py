"""The serve engine's per-request record, as the per-layer metrics read
it: one ``serve.request`` event per resolved request in
``obs.events()``, from its due time to its answer being ready, with the
offsets at which the serve loop admitted it and its group's dispatch
returned (``repro.fhe.serve.CkksServeEngine``)."""
from __future__ import annotations

REQUEST = "serve.request"


def requests(events: list) -> list[dict]:
    """Per request, in microseconds on the ``obs`` clock: ``due``,
    ``admitted``, ``dispatched``, ``ready``, and its ``batch`` (None
    where it was resolved at admission)."""
    out = []
    for ev in events:
        if ev["name"] == REQUEST:
            a, due = ev["args"], ev["ts_us"]
            out.append({"due": due, "admitted": due + a["admitted_us"],
                        "dispatched": due + a["dispatched_us"],
                        "ready": due + ev["dur_us"], "batch": a["batch"]})
    return out


def dispatched(events: list) -> list[dict]:
    """The requests that rode a dispatched group."""
    return [r for r in requests(events) if r["batch"] is not None]
