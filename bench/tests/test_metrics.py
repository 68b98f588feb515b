"""Each per-layer reader under ``metrics/``, on a window made by hand."""

import pytest

import harness

SNAP = {"sched.steps": 8, "sched.active_lane_steps": 60, "cache.hits": 3,
        "cache.misses": 1,
        "endpoint.queue_wait_s": {"count": 4, "p95": 0.25}}
TRACE = {"busy_s": 9.0, "window_s": 10.0, "kernel_s": {"probe": 6.0},
         "breakdown": {}}


def window(trace=TRACE):
    """Four requests sent in the 10 s window, answered 2.5 s apart, the
    last 10 s after the open (a rate of 0.4/s: 4 queries in the window's
    length), and one sent after the close, which is not due."""
    recs = [harness.Record(inst=None, sent=100.0 + i, done=102.5 + 2.5 * i,
                           status="ok") for i in range(4)]
    recs.append(harness.Record(inst=None, sent=110.5, done=111.0,
                               status="ok"))
    return harness.Window("c", 10.0, 100.0, recs, SNAP,
                          {"backend_compiles": 2}, trace)


@pytest.mark.parametrize("name, trace, want", [
    ("lanes_per_step", TRACE, 7.5),
    ("steps_per_query", TRACE, 2.0),
    ("probe_ms_per_query", TRACE, 1500.0),
    ("probe_ms_per_query", None, None),
    ("idle_share.closed", TRACE, 10.0),
    ("idle_share.closed", None, None),
    ("compiles_in_window", TRACE, 2),
    ("p95_s.closed", TRACE, 7.0),
    ("p95_s.closed", None, 7.0),
])
def test_reader(name, trace, want):
    got = harness.read_metric(name, window(trace))
    assert got == pytest.approx(want) if want is not None else got is None


def test_rate_is_continuous():
    """The rate counts the answers due in the window over the time they
    took, so a later last answer lowers it however the answers group."""
    w = window()
    assert w.queries_per_s() == pytest.approx(0.4)
    w.records[3].done += 1.0
    assert w.queries_per_s() == pytest.approx(4 / 11.0)
    assert len(w.due()) == 4


@pytest.mark.parametrize("status", ["rejected", "error", "unanswered"])
def test_not_ok_counts_unanswered(status):
    """A request due in the window and not answered ``ok`` is unanswered,
    a rejection too; one sent after the close is not due."""
    w = window()
    for r in w.records:
        r.status = status
    got = harness.check(w, answers=None)
    assert got["unanswered"] == {"value": 4, "limit": 0}
    assert got["wrong_answers"] == {"value": 0, "limit": 0}


def test_tail_of_unanswered_is_none():
    """A request due and not answered ``ok`` has no latency: where one
    reaches the 95th rank, the tail reader reports nothing."""
    w = window()
    w.records[0].status = "error"
    assert harness.read_metric("p95_s.closed", w) is None
    w.records[0].status = "ok"
    assert harness.read_metric("p95_s.closed", w) == pytest.approx(7.0)
