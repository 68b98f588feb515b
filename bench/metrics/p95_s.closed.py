"""Scheduler: the 95th percentile (nearest rank) of send-to-answer
seconds over every request due in the window, in a closed-loop cell.  A
drain's answers come together at its end, so this is the slowest of the
window's drains: one host stall in any drain is the reading.  None where
a request due was not answered ``ok`` far enough to reach the rank."""

import math

from harness import quantile


def read(w):
    v = quantile(w.latencies(), 0.95)
    return v if math.isfinite(v) else None
