"""Scheduler: blocking device-to-host transfers the scheduler made per
dispatched unit step over the window, ``sched.host_syncs / sched.steps``
(digest, stat, insertion, checkpoint and finalize pulls alike).  None
where the program does not count them."""


def read(w):
    syncs = w.snap.get("sched.host_syncs")
    steps = w.snap.get("sched.steps", 0)
    return syncs / steps if syncs is not None and steps else None
