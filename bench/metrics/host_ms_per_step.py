"""Scheduler: host milliseconds a dispatched unit step costs over the
window: the host wall of the waves' units and finalizes less the time
the host waited on unit-step results, ``1e3 * (sched.span_s.unit +
sched.span_s.wave.finalize - sched.span_s.unit.wait) / sched.steps``.
Units, not whole waves: a span's seconds count when it closes, so a
wave still running at the window's close would drop its wall while its
finished units' waits still count.  Read in traced runs, where the
program's spans are on; None where the program has no such spans."""


def read(w):
    unit = w.snap.get("sched.span_s.unit")
    steps = w.snap.get("sched.steps", 0)
    if unit is None or not steps:
        return None
    host = unit + w.snap.get("sched.span_s.wave.finalize", 0.0) \
        - w.snap.get("sched.span_s.unit.wait", 0.0)
    return 1e3 * host / steps
