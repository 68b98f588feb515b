"""Pallas TPU kernel: device-side fragment replay (cache-hit scatter-delta).

PR 4 made the scheduler's fragment cache digest-first: unit steps ship
16-byte fingerprints to the host instead of Omega blocks.  But the *replay*
of a hit still ran on the host — an all-hit wave pulled its entire state
down, applied the cached deltas in numpy, and (on the next miss) pushed it
back up.  This kernel closes that loop: the cached delta ``(src_row,
written)`` is uploaded (it is the small object — tens of bytes per row
against the full Omega block) and scattered onto the lane's device-resident
seed prefix in place, so all-hit waves never materialise binding tables on
the host at all.

The replayed output row ``j`` is ``seed[src[j]]`` with the unit's write
columns overwritten by ``written[j]`` — a gather by row index.  TPU has no
efficient per-row dynamic gather from VMEM, so the kernel uses the same
broadcast-compare-reduce scheme as ``run_probe``/``sorted_probe``: stream
the seed table through VMEM in row tiles; for every output row j and seed
tile compute on the VPU

    hit[j, i] = (src[j] == i_abs)          i_abs = global seed row index
    out[j, :] = sum_tiles sum_i hit[j, i] * seed_tile[i, :]

Each ``src[j]`` matches exactly one seed row (valid-prefix indices), so the
masked sum IS the gather — in int32 throughout (float accumulation would
corrupt dictionary ids above 2^24).  Padding output rows carry ``src = -1``
and match nothing.  The write-column overlay, UNBOUND masking of the dead
tail and the validity prefix are applied by the wrapper outside the kernel
(same split as ``fingerprint_rows_pallas``'s finalize), so the jnp oracle
``ref.replay_delta_ref``, this kernel, and the numpy twin
``fragcache.replay`` share the exact same tail semantics.

Layout: the compare is laid out *transposed* — seed rows on the sublanes,
output rows on the lanes.  ``src`` streams as a lane-dense ``(1, J_TILE)``
row, the seed tile keeps its natural ``(I_TILE, V)`` shape (a column
slice ``seed[:, c]`` is then a sublane vector that broadcasts across the
lanes for free), and the kernel writes a ``(V, J_TILE)`` output block that
the wrapper transposes back.  Each column is reduced separately over the
sublanes, so no 3-D (and no i1 3-D) intermediate exists: Mosaic cannot
reshape an i1 vector to 3-D.

Grid: (num_out_tiles, num_seed_tiles); TPU grids iterate the last axis
fastest and sequentially, so partial gathers accumulate in the output block
across seed-tile steps (init at i == 0).
"""

from __future__ import annotations

import functools

import jax
import numpy as np
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import ZERO, as_row

DEFAULT_J_TILE = 256  # output (delta) rows per tile
DEFAULT_I_TILE = 512  # seed rows streamed per tile


def _replay_kernel(src_ref, seed_ref, out_ref):
    i = pl.program_id(1)
    src = src_ref[...]  # [1, J_TILE] int32
    seed = seed_ref[...]  # [I_TILE, V] int32
    i_tile, n_vars = seed.shape
    # global seed row index per (tile element, out row): [I_TILE, J_TILE]
    i_abs = i * i_tile + jax.lax.broadcasted_iota(
        jnp.int32, (i_tile, 1), 0)
    hit = i_abs == src
    partial = jnp.concatenate(
        [jnp.sum(jnp.where(hit, seed[:, c:c + 1], np.int32(0)), axis=0,
                 keepdims=True, dtype=jnp.int32)  # int32: x64 must not widen
         for c in range(n_vars)], axis=0)  # [V, J_TILE]

    @pl.when(i == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(i != 0)
    def _accum():
        out_ref[...] = out_ref[...] + partial


@functools.partial(jax.jit,
                   static_argnames=("write_cols", "j_tile", "i_tile",
                                    "interpret"))
def replay_delta_pallas(seed_rows: jnp.ndarray, src: jnp.ndarray,
                        written: jnp.ndarray, n_out: jnp.ndarray,
                        write_cols: tuple[int, ...] = (),
                        j_tile: int = DEFAULT_J_TILE,
                        i_tile: int = DEFAULT_I_TILE,
                        interpret: bool = False
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Device-side fragment replay (see module docstring).

    Same contract as ``ref.replay_delta_ref``: ``seed_rows`` int32[cap, V]
    (valid prefix = the unit's input), ``src`` int32[M] delta source rows
    (entries past ``n_out`` are padding), ``written`` int32[M, W] values
    for ``write_cols``, ``n_out`` the true output count.  Returns the
    full-capacity replayed ``(rows, valid)``.
    """
    cap, n_vars = seed_rows.shape
    m = src.shape[0]
    live = jnp.arange(m, dtype=jnp.int32) < n_out
    # padding/dead src entries match no seed row inside the kernel
    src_k = jnp.where(live, src.astype(jnp.int32), jnp.int32(-1))
    src_p = as_row(src_k, j_tile, -1)
    seed_p = jnp.pad(seed_rows, ((0, -cap % i_tile), (0, 0)))
    grid = (src_p.shape[1] // j_tile, seed_p.shape[0] // i_tile)
    out_t = pl.pallas_call(
        _replay_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, j_tile), lambda j, i: (ZERO, j)),
            pl.BlockSpec((i_tile, n_vars), lambda j, i: (i, ZERO)),
        ],
        out_specs=pl.BlockSpec((n_vars, j_tile), lambda j, i: (ZERO, j)),
        out_shape=jax.ShapeDtypeStruct((n_vars, src_p.shape[1]), jnp.int32),
        interpret=interpret,
        name="replay_delta",
    )(src_p, seed_p)
    out = out_t.T[:m]
    # shared tail (identical to the oracle): write-col overlay, dead-row
    # UNBOUND fill, prefix validity
    for w, c in enumerate(write_cols):
        out = out.at[:, c].set(written[:, w])
    out = jnp.where(live[:, None], out, jnp.int32(-1))
    rows = jnp.full((cap, n_vars), -1, jnp.int32).at[:m].set(out)
    valid = jnp.arange(cap, dtype=jnp.int32) < n_out
    return rows, valid
