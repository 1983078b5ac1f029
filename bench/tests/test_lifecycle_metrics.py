"""The per-layer metrics that read the serve engine's ``serve.request``
events and the plan's ``plan.batch_key`` spans, on synthetic spans with
hand-computed values, and their silence where the spans are absent."""
import pytest

from harness import spec


def _read(name, observed):
    return spec.metric_reader(name)(observed)


def _req(rid, due, adm, disp, ready, batch):
    """A serve.request event; times in ms on the obs clock."""
    return {"name": "serve.request", "ts_us": due * 1e3,
            "dur_us": (ready - due) * 1e3, "tid": 1, "depth": 1,
            "forwarded": False,
            "args": {"rid": rid, "kind": "rotate",
                     "admitted_us": (adm - due) * 1e3,
                     "dispatched_us": (disp - due) * 1e3, "batch": batch}}


def _span(name, start_ms, end_ms, forwarded=True, **args):
    return {"name": name, "ts_us": start_ms * 1e3,
            "dur_us": (end_ms - start_ms) * 1e3, "tid": 1, "depth": 0,
            "forwarded": forwarded, "args": args}


# due, admitted, dispatched, ready (ms)
REQS = [_req(0, 0.0, 0.5, 2.0, 10.0, 1),
        _req(1, 1.0, 1.0, 2.0, 10.0, 1),
        _req(2, 4.0, 6.0, 12.0, 20.0, 2),
        _req(3, 5.0, 5.0, 5.0, 5.0, None)]      # resolved at admission


def test_admit_lag_p99():
    # lags 0.5, 0, 2, 0 ms; p99 interpolates between 0.5 and 2 at 0.97
    got = _read("serve.admit_lag_p99_ms", {"spans": REQS})
    assert got == pytest.approx(0.5 + 0.97 * 1.5)


def test_queue_and_inflight_medians_skip_requests_without_a_batch():
    # dispatched - admitted: 1.5, 1.0, 6.0 -> 1.5
    assert _read("serve.queue_ms", {"spans": REQS}) == pytest.approx(1.5)
    # ready - dispatched: 8, 8, 8 -> 8
    assert _read("serve.inflight_ms", {"spans": REQS}) == pytest.approx(8.0)


def test_batch_key_miss_pct():
    spans = [_span("plan.batch_key", 0, 1, hit=h, b=8)
             for h in (False, True, True, False, True)]
    got = _read("plan.batch_key_miss_pct", {"spans": spans})
    assert got == pytest.approx(40.0)


@pytest.mark.parametrize("name", [
    "serve.admit_lag_p99_ms", "serve.queue_ms", "serve.inflight_ms",
    "plan.batch_key_miss_pct"])
def test_silent_without_their_spans(name):
    """A program that records none of these spans (an older engine, or a
    cell that serves no CKKS request) gives no reading, and no error."""
    old = [{"name": "serve.run", "ts_us": 0.0, "dur_us": 10.0, "tid": 1,
            "depth": 0, "args": {}}]
    trace = {"devices": 1, "busy_s": 0.0, "window_s": 0.0, "ops": [],
             "gaps": []}
    assert _read(name, {"spans": old, "trace": trace}) is None
    assert _read(name, {"spans": [], "trace": None}) is None
