"""Scheduler: live (non-padding) lanes per dispatched unit step over the
window, ``sched.active_lane_steps / sched.steps``."""


def read(w):
    steps = w.snap.get("sched.steps", 0)
    return w.snap.get("sched.active_lane_steps", 0) / steps if steps else None
