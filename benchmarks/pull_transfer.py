"""What it costs to bring a unit step's outputs to the host, on a TPU.

Two ways the scheduler can record its missed lanes (``core/scheduler.py``):

- ``lane pair``: one lane's ``rows[j, :n]`` and ``src[j, :n]`` pulled on
  their own, each a slice program on the device and a blocking transfer;
  a wave of all-missed lanes pays it ``B`` times;
- ``wave get``: the whole wave's rows ``[B, cap, V]`` and provenance
  ``[B, cap]`` with the step's four ``[B]`` stat arrays, in one
  ``jax.device_get``.

Beside them, ``stats get`` is the stats alone in one ``device_get`` and
``stat pulls`` the four pulled one by one, as the scheduler did before.
Each array is made afresh before every timed call (a ``jax.Array`` keeps
its host copy once fetched), and every program is compiled before the
timing.  Prints one JSON line a shape, the median milliseconds over
``REPS`` calls, and writes them to ``chiprun_out/pull_transfer.json``::

    PYTHONPATH=src python -m benchmarks.pull_transfer

It needs a TPU and does not fall back.  ``scheduler.WAVE_PULL_BYTES`` was
set from its numbers (``PERF.md``).
"""

from __future__ import annotations

import json
import statistics
import time
from pathlib import Path

#: (lanes, capacity, variables): the benchmark cells' wave and their
#: ``max_cap`` rung, then larger rungs toward the engine's default
#: ``max_cap`` of 1,048,576 rows
SHAPES = ((8, 1024, 9), (8, 16384, 9), (8, 65536, 9), (8, 262144, 9),
          (8, 1048576, 9))
ROWS = 64  # the prefix a lane pull slices: the cells' ``fit_rows``
REPS = 30


def _median_ms(fn, fresh) -> float:
    times = []
    for _ in range(REPS):
        arrays = fresh()
        t = time.perf_counter()
        fn(arrays)
        times.append(time.perf_counter() - t)
    return 1e3 * statistics.median(times)


def measure(B: int, cap: int, V: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    base = (jnp.zeros((B, cap, V), jnp.int32), jnp.zeros((B, cap), jnp.int32),
            *(jnp.zeros((B,), jnp.int32) for _ in range(4)))
    bump = jax.jit(lambda xs: tuple(x + 1 for x in xs))

    def fresh():
        return jax.block_until_ready(bump(base))

    def lane_pair(a, j=0):
        np.asarray(a[0][j, :ROWS])
        np.asarray(a[1][j, :ROWS])

    def wave_lanes(a):
        for j in range(B):
            lane_pair(a, j)

    def wave_get(a):
        jax.device_get(a[2:] + a[:2])

    def stats_get(a):
        jax.device_get(a[2:])

    def stat_pulls(a):
        for x in a[2:]:
            np.asarray(x)

    wave_lanes(fresh())  # compile every slice program first
    row = {"shape": [B, cap, V], "wave_bytes": B * cap * (V + 1) * 4}
    for name, fn in (("lane_pair_ms", lane_pair),
                     ("wave_lanes_ms", wave_lanes),
                     ("wave_get_ms", wave_get), ("stats_get_ms", stats_get),
                     ("stat_pulls_ms", stat_pulls)):
        row[name] = _median_ms(fn, fresh)
    return row


def main() -> None:
    from repro.chip import require_tpu

    dev = require_tpu()
    rows = []
    for shape in SHAPES:
        row = measure(*shape)
        row["device"] = dev.device_kind
        print(json.dumps(row), flush=True)
        rows.append(row)
    out = Path("chiprun_out")
    out.mkdir(exist_ok=True)
    (out / "pull_transfer.json").write_text(json.dumps(rows, indent=1))


if __name__ == "__main__":
    main()
