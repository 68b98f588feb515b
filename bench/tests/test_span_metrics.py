"""The readers of the scheduler's host syncs and span seconds, on
windows made by hand: with the program's counters and spans, and without
them, as a program that lacks them reports."""

import pytest

import harness

SNAP = {"sched.steps": 8, "sched.active_lane_steps": 64,
        "sched.host_syncs": 176, "sched.span_s.wave": 1.5,
        "sched.span_s.unit.wait": 1.1, "sched.span_s.unit": 1.45,
        "sched.span_s.wave.finalize": 0.05}


def window(snap):
    recs = [harness.Record(inst=None, sent=100.0 + i, done=102.5 + 2.5 * i,
                           status="ok") for i in range(4)]
    return harness.Window("c", 10.0, 100.0, recs, snap,
                          {"backend_compiles": 0}, None)


@pytest.mark.parametrize("name, snap, want", [
    ("host_syncs_per_step", SNAP, 22.0),
    ("host_ms_per_step", SNAP, 50.0),
    # the units' and finalizes' host wall alone where no step was waited on
    ("host_ms_per_step", {k: v for k, v in SNAP.items()
                          if k != "sched.span_s.unit.wait"}, 187.5),
    # a wave still open at the close adds no wave seconds and changes
    # nothing: the reader sums units and finalizes
    ("host_ms_per_step", {**SNAP, "sched.span_s.wave": 0.9}, 50.0),
    # a program without the counter or the spans: nothing to read
    ("host_syncs_per_step", {"sched.steps": 8}, None),
    ("host_ms_per_step", {"sched.steps": 8, "sched.host_syncs": 176}, None),
    # no unit step in the window
    ("host_syncs_per_step", {**SNAP, "sched.steps": 0}, None),
    ("host_ms_per_step", {**SNAP, "sched.steps": 0}, None),
])
def test_span_reader(name, snap, want):
    got = harness.read_metric(name, window(snap))
    assert got == pytest.approx(want) if want is not None else got is None


def test_readers_listed_for_the_cold_cells():
    """Both readers are per-layer metrics of the two cold cells, so
    their traced runs report them."""
    for cell in ("spf-2stars-cold", "brtpf-2stars-cold"):
        names = {m["name"] for m in harness.load_cell(cell).per_layer}
        assert {"host_syncs_per_step", "host_ms_per_step"} <= names
