"""Pallas TPU kernel: fused membership + rank of targets within per-row runs.

The second half of the SPF server's hot loop.  After ``eqrange`` locates a
branch's ``(p, s)`` run ``values[lo_i:hi_i)`` for every binding row, the
probe branches (Def. 5 bind-join, object bound) must test whether the row's
object value occurs inside its run — one independent sorted-run lookup per
row.  The seed implementation was a serial ``fori_loop`` bisection
(``searchsorted_in_runs``): O(log n) *dependent* scalar steps, each a
per-lane gather — the worst possible shape for the VPU.

TPU adaptation: same tile/broadcast-compare-reduce scheme as
``sorted_probe``, with a per-row window mask.  Stream ``values`` through
VMEM in tiles; for every row r and value tile j compute on the VPU

    in_run  = (lo_r <= k_abs) & (k_abs < hi_r)        k_abs = global index
    pos(r)  = lo_r + sum_tiles sum(in_run & (tile < target_r))
    hit(r)  = or_tiles  any(in_run & (tile == target_r))

i.e. ``pos`` is the absolute "left" insertion position of ``target_r`` in
its run and ``hit`` its membership — exactly what ``run_contains`` needs,
in one fused pass with a coalesced HBM->VMEM stream and zero gathers.  The
window mask makes value padding a non-issue: padded positions sit at
``k_abs >= n >= hi_r`` and padded rows get the empty run ``[0, 0)``.

Layout (``kernels.layout``): values stream as a lane-dense ``(1, V_TILE)``
row; ``lo``/``hi``/``targets`` and both outputs are ``(R_TILE, 1)``
columns.  Membership is an int32 0/1 max-reduction and the kernel emits
it as int32 (the wrapper converts): Mosaic has no bool outputs, and under
``jax_enable_x64`` ``jnp.any`` lowers to a float64 reduction it refuses.

Grid: (num_r_tiles, num_v_tiles); TPU grids iterate the last axis fastest
and sequentially, so partial ranks accumulate in the output block across
value-tile steps (init at j == 0).  ``broadcasted_iota`` is 2D — TPU
rejects 1D iota.

Scalar-prefetch variant (``run_probe_prefetch_pallas``): the dense grid
streams the *entire* value column past every row tile, but a binding
row's run is tiny relative to the column — most tiles intersect no run of
the block.  The prefetch variant computes, per row block, the index of
the first and last value tile any non-empty run touches (two int32 arrays
of length ``num_r_tiles``, handed to ``PrefetchScalarGridSpec`` so they
are resident before the pipeline starts) and maps the value-tile axis
*through* that window: the BlockSpec index map returns
``base[i] + min(j, nwin[i]-1)``, so grid steps past the window re-request
the window's last tile — and Pallas skips the copy when consecutive block
indices are equal, so value tiles no row in the block touches are never
streamed from HBM.  A ``pl.when(j < nwin[i])`` guard keeps the repeated
tile out of the accumulation, so the contract is bit-identical to the
dense kernel.  Empty runs (``hi <= lo`` — including the sharded path's
non-owned rows, which ``eqrange_owned`` collapses to ``[lo, lo)``)
contribute nothing to the window, so a block of them streams zero tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import ZERO, as_col, as_row


DEFAULT_R_TILE = 256
DEFAULT_V_TILE = 2048


def _window_partials(values, lo, hi, targets, tile):
    """One value tile's ``(sum(lt), max(eq))`` over each row's run window.

    ``values`` is the ``[1, V_TILE]`` tile whose first element sits at
    absolute index ``tile * V_TILE``; ``lo``/``hi``/``targets`` are
    ``[R_TILE, 1]`` columns.  Both partials are int32 ``[R_TILE, 1]``.
    """
    v_tile = values.shape[1]
    k_abs = (tile * v_tile
             + jax.lax.broadcasted_iota(jnp.int32, (1, v_tile), 1))
    in_run = (k_abs >= lo) & (k_abs < hi)  # [R_TILE, V_TILE]
    lt = (in_run & (values < targets)).astype(jnp.int32)
    eq = (in_run & (values == targets)).astype(jnp.int32)
    return (jnp.sum(lt, axis=1, keepdims=True, dtype=jnp.int32),
            jnp.max(eq, axis=1, keepdims=True))


def _run_probe_kernel(values_ref, lo_ref, hi_ref, targets_ref,
                      pos_ref, contains_ref):
    j = pl.program_id(1)
    lo = lo_ref[...]
    partial_pos, partial_contains = _window_partials(
        values_ref[...], lo, hi_ref[...], targets_ref[...], j)

    @pl.when(j == 0)
    def _init():
        pos_ref[...] = lo + partial_pos
        contains_ref[...] = partial_contains

    @pl.when(j != 0)
    def _accum():
        pos_ref[...] = pos_ref[...] + partial_pos
        contains_ref[...] = jnp.maximum(contains_ref[...], partial_contains)


def _operands(values, lo, hi, targets, r_tile, v_tile):
    """Promote, pad and lay out the run_probe operands (shared by both
    variants).  Value padding uses the +max of the *promoted* dtype —
    promotion must happen before padding, or an int32 column probed by
    int64 targets would pad with int32-max values that promoted targets
    can exceed — and row padding the empty run ``[0, 0)``."""
    dt = jnp.promote_types(values.dtype, targets.dtype)
    maxval = jnp.iinfo(dt).max
    if values.shape[0] == 0:  # keep one (all-padding) tile in the grid
        values = jnp.full((1,), maxval, dt)
    values_p = as_row(values.astype(dt), v_tile, maxval)
    lo_p = as_col(lo.astype(jnp.int32), r_tile, 0)
    hi_p = as_col(hi.astype(jnp.int32), r_tile, 0)
    targets_p = as_col(targets.astype(dt), r_tile, 0)
    return values_p, lo_p, hi_p, targets_p


@functools.partial(jax.jit, static_argnames=("r_tile", "v_tile", "interpret"))
def run_probe_pallas(values: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                     targets: jnp.ndarray,
                     r_tile: int = DEFAULT_R_TILE,
                     v_tile: int = DEFAULT_V_TILE,
                     interpret: bool = False
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused per-row sorted-run probe.

    Returns ``(pos, contains)`` with

        pos[i]      = lo[i] + #{k in [lo[i], hi[i)) : values[k] < targets[i]}
        contains[i] = targets[i] in values[lo[i]:hi[i]]

    Each run ``values[lo_i:hi_i)`` must be individually sorted ascending
    (the PSO/POS store layout guarantees this).  Empty runs
    (``lo[i] == hi[i]``) yield ``pos == lo`` and ``contains == False``.
    Padding (see ``_operands``) is kept inert by the in-run window mask.
    """
    r = lo.shape[0]
    values_p, lo_p, hi_p, targets_p = _operands(values, lo, hi, targets,
                                                r_tile, v_tile)
    r_len = lo_p.shape[0]
    grid = (r_len // r_tile, values_p.shape[1] // v_tile)
    col = pl.BlockSpec((r_tile, 1), lambda i, j: (i, ZERO))
    pos, contains = pl.pallas_call(
        _run_probe_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, v_tile), lambda i, j: (ZERO, j)),
                  col, col, col],
        out_specs=[col, col],
        out_shape=[jax.ShapeDtypeStruct((r_len, 1), jnp.int32)] * 2,
        interpret=interpret,
        name="run_probe",
    )(values_p, lo_p, hi_p, targets_p)
    return pos[:r, 0], contains[:r, 0] != 0


def _run_probe_prefetch_kernel(base_ref, nwin_ref, values_ref, lo_ref,
                               hi_ref, targets_ref, pos_ref, contains_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    lo = lo_ref[...]
    nwin = nwin_ref[i]
    # the value tile actually resident: the index map clamps steps past the
    # window onto its last tile (whose copy Pallas then skips) — recompute
    # the same tile id here for the absolute-position arithmetic
    t = base_ref[i] + jnp.minimum(j, jnp.maximum(nwin - 1, 0))

    @pl.when(j == 0)
    def _init():
        pos_ref[...] = lo
        contains_ref[...] = jnp.zeros_like(lo)

    @pl.when(j < nwin)
    def _accum():
        partial_pos, partial_contains = _window_partials(
            values_ref[...], lo, hi_ref[...], targets_ref[...], t)
        pos_ref[...] = pos_ref[...] + partial_pos
        contains_ref[...] = jnp.maximum(contains_ref[...], partial_contains)


@functools.partial(jax.jit, static_argnames=("r_tile", "v_tile", "interpret"))
def run_probe_prefetch_pallas(values: jnp.ndarray, lo: jnp.ndarray,
                              hi: jnp.ndarray, targets: jnp.ndarray,
                              r_tile: int = DEFAULT_R_TILE,
                              v_tile: int = DEFAULT_V_TILE,
                              interpret: bool = False
                              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``run_probe_pallas`` with scalar-prefetched per-block tile windows.

    Same ``(pos, contains)`` contract, bit-identical results.  The grid
    stays the dense ``(num_r_tiles, num_v_tiles)`` — the windows are
    traced values, and the grid must be static — but the value-tile axis
    is mapped through the prefetched window, so steps outside a block's
    window neither stream a tile from HBM (the index map repeats the last
    window tile, which the pipeline recognises and skips) nor touch the
    VPU (the ``j < nwin`` guard).  The win is proportional to how sparse
    the touched windows are — the engine's common case, where a wave's
    runs cluster in a sliver of the column.
    """
    r = lo.shape[0]
    values_p, lo_p, hi_p, targets_p = _operands(values, lo, hi, targets,
                                                r_tile, v_tile)
    n_r_tiles = lo_p.shape[0] // r_tile
    n_v_tiles = values_p.shape[1] // v_tile

    # per row-block window of touched value tiles, over NON-empty runs
    # only: empty runs (hi <= lo — row padding, filtered rows, non-owned
    # rows under sharding) contribute nothing, so an all-empty block gets
    # nwin == 0 and streams zero value tiles
    lo_f, hi_f = lo_p[:, 0], hi_p[:, 0]
    nonempty = hi_f > lo_f
    lo_t = jnp.where(nonempty, lo_f // v_tile, jnp.int32(n_v_tiles))
    hi_t = jnp.where(nonempty, (hi_f - 1) // v_tile, jnp.int32(-1))
    blk_lo = jnp.min(lo_t.reshape(n_r_tiles, r_tile), axis=1)
    blk_hi = jnp.max(hi_t.reshape(n_r_tiles, r_tile), axis=1)
    base = jnp.where(blk_hi >= blk_lo, blk_lo, 0).astype(jnp.int32)
    nwin = jnp.maximum(blk_hi - blk_lo + 1, 0).astype(jnp.int32)

    def value_map(i, j, base, nwin):
        return (ZERO, base[i] + jnp.minimum(j, jnp.maximum(nwin[i] - 1, 0)))

    col = pl.BlockSpec((r_tile, 1), lambda i, j, b, w: (i, ZERO))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_r_tiles, n_v_tiles),
        in_specs=[pl.BlockSpec((1, v_tile), value_map), col, col, col],
        out_specs=[col, col],
    )
    pos, contains = pl.pallas_call(
        _run_probe_prefetch_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(lo_p.shape, jnp.int32)] * 2,
        interpret=interpret,
        name="run_probe_prefetch",
    )(base, nwin, values_p, lo_p, hi_p, targets_p)
    return pos[:r, 0], contains[:r, 0] != 0
