"""2-D block layouts shared by the probe kernels.

Mosaic lowers 2-D blocks whose last dimension is lane-dense (a multiple of
128, or the array's full extent) and whose second-to-last is a multiple of
8 (or the full extent).  The probe kernels all compare a batch of per-row
operands against a stream of column values, so they use two shapes:

- a **row** ``(1, n)``: the streamed column (keys, values), lane-dense —
  broadcasting it down the sublanes of a ``[rows, tile]`` compare is free;
- a **column** ``(n, 1)``: the per-row operands (queries, run bounds,
  targets) — broadcasting it across lanes is free, and a lane reduction
  of the compare lands back in the same ``(rows, 1)`` shape.

``ZERO`` is the block index for a dimension that is not tiled.  A bare
Python ``0`` in an index map is an int64 constant under ``jax_enable_x64``,
and Mosaic refuses 64-bit index maps.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

ZERO = np.int32(0)


def as_row(x: jnp.ndarray, tile: int, fill) -> jnp.ndarray:
    """``x`` padded with ``fill`` to a multiple of ``tile``, shaped (1, n)."""
    return jnp.pad(x, (0, -x.shape[0] % tile),
                   constant_values=fill).reshape(1, -1)


def as_col(x: jnp.ndarray, tile: int, fill) -> jnp.ndarray:
    """``x`` padded with ``fill`` to a multiple of ``tile``, shaped (n, 1)."""
    return jnp.pad(x, (0, -x.shape[0] % tile),
                   constant_values=fill).reshape(-1, 1)
