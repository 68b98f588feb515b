"""Scheduler: unit steps dispatched in the window per query the window's
rate answers in its length, ``sched.steps / Window.queries_answered``."""


def read(w):
    n = w.queries_answered()
    return w.snap.get("sched.steps", 0) / n if n else None
