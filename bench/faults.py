"""Faults planted under the timed path, to show that the check catches them.

Each fault wraps the window's scheduler (``svc.sched.drain``, where the
answers are produced) and breaks what it returns.  The benchmark's own
runs plant none; ``control.py`` and ``tests/test_rehearsal.py`` do.

- ``truncated`` is the control: it breaks the configuration's guarantee
  of exact answers the way an approximate server would, by dropping the
  last row of every answer that has more than one;
- ``altered``: one value of the first non-empty answer of each drain is
  changed;
- ``dropped_half``: every second answer of each drain is left out, as if
  half of the batch were never computed.
"""

from __future__ import annotations

import numpy as np


def _wrap(svc, change):
    drain = svc.sched.drain

    def broken_drain():
        results = drain()
        change(results)
        return results

    svc.sched.drain = broken_drain


def truncated(svc) -> None:
    def change(results):
        for table, _ in results.values():
            if table is None:
                continue
            valid = np.asarray(table.valid)
            live = np.nonzero(valid)[0]
            if live.shape[0] > 1:
                valid[live[-1]] = False

    _wrap(svc, change)


def altered(svc) -> None:
    def change(results):
        for table, _ in results.values():
            if table is not None:
                live = np.nonzero(np.asarray(table.valid))[0]
                if live.shape[0]:
                    np.asarray(table.rows)[live[0], 0] += 1
                    return

    _wrap(svc, change)


def dropped_half(svc) -> None:
    def change(results):
        for rid in sorted(results)[::2]:
            del results[rid]

    _wrap(svc, change)


FAULTS = {"truncated": truncated, "altered": altered,
          "dropped_half": dropped_half}
