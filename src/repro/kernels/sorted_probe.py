"""Pallas TPU kernel: batched probe of query keys into a sorted key run.

This is the SPF server's hot loop.  Star-pattern evaluation reduces to, per
branch, locating every candidate subject inside the branch's sorted
predicate run (``searchsorted``) and testing membership — millions of
probes against runs of 10^3..10^6 keys.

TPU adaptation (vs. the CPU/Java original and vs. a GPU port): scalar
binary search is hostile to the VPU (8x128 lanes, no per-lane branching),
and per-lane gather from HBM is the slowest path on TPU.  Instead we
stream the run through VMEM in tiles and compute, for every query key,

    rank_lo(q)  = sum_tiles  sum(tile_keys <  q)    (= searchsorted left)
    rank_hi(q)  = sum_tiles  sum(tile_keys <= q)    (= searchsorted right)
    contains(q) = rank_lo(q) < rank_hi(q)

i.e. probe-by-broadcast-compare-reduce: a dense [Q_tile x K_tile] compare on
the VPU per grid step.  For run lengths up to ~10^6 this linear-scan-in-
vector-registers beats the log-n scalar loop on TPU by orders of magnitude
(the MXU is idle either way; the VPU does 8x128 compares/cycle), and it has
a perfectly predictable, coalesced HBM->VMEM stream.  Complexity is
O(N*Q / 1024) VPU ops versus O(Q log N) *serial* scalar ops.

Emitting both rank sides from one kernel pass is what lets the engine's
``eqrange`` (equal-range lookup, the per-branch run locator) lower to a
single fused probe instead of two searchsorted calls — see
``repro.kernels.ops.eqrange``.

Layout (``kernels.layout``): keys stream as a lane-dense ``(1, K_TILE)``
row and queries sit in a ``(Q_TILE, 1)`` column, so the compare is a plain
2-D broadcast and each lane reduction lands in the query column.  Keys and
queries reach the kernel as int32 on the chip (Mosaic has no 64-bit
vectors; ``rdf.store`` keeps its composite keys in int32), and every
reduction names its int32 dtype — under ``jax_enable_x64`` an unnamed
``jnp.sum`` would widen to int64.

Grid: (num_q_tiles, num_k_tiles); TPU grids iterate the last axis fastest
and sequentially, so the kernel accumulates partial ranks in the output
block across k-tile steps (init at j == 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import ZERO, as_col, as_row


DEFAULT_Q_TILE = 256
DEFAULT_K_TILE = 2048


def _probe_kernel(keys_ref, queries_ref, rank_lo_ref, rank_hi_ref):
    j = pl.program_id(1)
    keys = keys_ref[...]  # [1, K_TILE]
    qs = queries_ref[...]  # [Q_TILE, 1]

    # dense compare: [Q_TILE, K_TILE] on the VPU, reduced over the lanes
    partial_lo = jnp.sum((keys < qs).astype(jnp.int32), axis=1,
                         keepdims=True, dtype=jnp.int32)
    partial_hi = jnp.sum((keys <= qs).astype(jnp.int32), axis=1,
                         keepdims=True, dtype=jnp.int32)

    @pl.when(j == 0)
    def _init():
        rank_lo_ref[...] = partial_lo
        rank_hi_ref[...] = partial_hi

    @pl.when(j != 0)
    def _accum():
        rank_lo_ref[...] = rank_lo_ref[...] + partial_lo
        rank_hi_ref[...] = rank_hi_ref[...] + partial_hi


@functools.partial(jax.jit, static_argnames=("q_tile", "k_tile", "interpret"))
def sorted_probe_pallas(keys: jnp.ndarray, queries: jnp.ndarray,
                        q_tile: int = DEFAULT_Q_TILE,
                        k_tile: int = DEFAULT_K_TILE,
                        interpret: bool = False
                        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused equal-range probe of ``queries`` into sorted ``keys``.

    Returns ``(rank_lo, rank_hi, contains)`` with

        rank_lo[i]  = #{k in keys : k <  queries[i]}   (searchsorted "left")
        rank_hi[i]  = #{k in keys : k <= queries[i]}   (searchsorted "right")
        contains[i] = queries[i] in keys

    ``keys`` must be sorted ascending.  Both arrays are padded to tile
    multiples; key padding uses +max, which is invisible to any query
    below the dtype max.  A query *equal* to the dtype max would see the
    padding in the ``<=`` compare, so the wrapper clamps ``rank_hi`` to
    ``n`` (the true right-rank at the max query is always ``n``) and
    derives ``contains`` as ``rank_lo < rank_hi`` — exact for every query
    value, keeping byte-parity with the jnp oracle unconditional.
    """
    n = keys.shape[0]
    q = queries.shape[0]
    dt = jnp.promote_types(keys.dtype, queries.dtype)
    maxval = jnp.iinfo(dt).max
    keys_p = as_row(keys.astype(dt), k_tile, maxval)
    queries_p = as_col(queries.astype(dt), q_tile, maxval)
    q_len = queries_p.shape[0]

    grid = (q_len // q_tile, keys_p.shape[1] // k_tile)
    col = pl.BlockSpec((q_tile, 1), lambda i, j: (i, ZERO))
    rank_lo, rank_hi = pl.pallas_call(
        _probe_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, k_tile), lambda i, j: (ZERO, j)), col],
        out_specs=[col, col],
        out_shape=[jax.ShapeDtypeStruct((q_len, 1), jnp.int32)] * 2,
        interpret=interpret,
        name="sorted_probe",
    )(keys_p, queries_p)
    rank_lo = rank_lo[:q, 0]
    rank_hi = jnp.minimum(rank_hi[:q, 0], n)
    return rank_lo, rank_hi, rank_lo < rank_hi
