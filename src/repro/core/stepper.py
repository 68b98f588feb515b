"""Shared unit-stepped execution machinery (engine ladder + scheduler waves).

PR 2 gave the scheduler a per-unit stepped execution path built on
``distributed.make_batch_step``; PR 4's resumable overflow gives the serial
engine one too (``QueryEngine.run`` re-enters at the overflowed unit instead
of re-running the whole query), so the step factories, their module-level
compile cache and the host twin of the traced cost accounting now live here
where both can reach them without an import cycle (this module imports
``distributed`` which imports ``engine``; ``engine`` imports this module
lazily at call time).

PR 5 collapses the replicated-lane / sharded-lane split into this seam:
the per-unit ``all_gather``/``psum`` collective that used to live inside
``DistributedEngine.make_step``'s whole-query lane evaluator
(``distributed._lane_eval``) is hoisted here as ``eval_unit_sharded`` +
``gather_merge``, so serial ``run``, ``run_batch``, vmapped waves,
replicated mesh waves and sharded mesh waves are all instantiations of one
unit evaluator — the lowering (and its collective schedule, or absence)
is the only difference.

Contents:

- ``unit_step``        — the scheduler's wave step: per-lane seeded unit
  evaluation with a provenance column (src-row extraction for replayable
  cache deltas) returning per-lane ``(rows, valid, overflow, src, ops,
  count, peak)``; vmap on one host, replicated-store shard_map across
  mesh lanes.
- ``sharded_unit_step`` — the same wave step over a subject-hash sharded
  store: each shard evaluates the unit's branches locally
  (``eval_unit_sharded`` — star locality makes branch joins
  collective-free), scalar psums recover the exact serial cost account,
  and a per-unit gather-merge rebuilds the lane table in *serial row
  order* (key: provenance + the unit's drawn-value columns), so sharded
  waves are byte-identical to the vmap/replicated lowerings — including
  the overflow flag, which is derived from the *global* expansion totals.
  Two bit-identical merge strategies (``select_gather_merge``): the
  replicated lexsort over the ``all_gather``'d block, and the k-way merge
  — ``log2(n_shards)`` ``ppermute`` rounds of pairwise
  ``merge_sorted_blocks``, linear rank-and-scatter work per round instead
  of a full sort.  Overflow-latch waves (``cap == max_cap``) merge after
  every branch so mid-unit truncation happens in global serial order —
  latch-rung waves stay sharded instead of falling back to
  replicated/vmap.
- ``serial_unit_step`` — the engine's ladder step: ``unit_step`` without
  the provenance column (serial ``run`` never inserts into the cache).
- ``digest_step``      — jitted wave fingerprinting: gathers a unit's read
  columns and hashes every lane's valid prefix on device
  (``kops.fingerprint_rows``), so the fragment cache is consulted with a
  16-byte digest per lane instead of a host round trip of the Omega block.
- ``replay_step``      — jitted wave-wide device-side cache-hit replay
  (``kops.replay_delta``): cached fragment deltas are uploaded and
  scattered onto the lanes' seed prefixes in place, so all-hit waves
  never materialise Omega blocks on the host.
- ``reseat``           — capacity regrow/shrink of a compacted table
  (resumable overflow grows exactly one unit's table; the valid prefix is
  preserved, the new tail is UNBOUND-filled).
- ``unit_cost``        — host twin of ``engine._execute``'s per-unit cost
  accounting, shared by the scheduler and the planned serial path (drift
  is pinned by the scheduler/serial stats-parity tests).

All step caches key on trace statics including ``kops.FORCE`` and the
kernel circuit breaker's ``kops.BREAKER.generation`` (both read at trace
time — the generation key is what makes a breaker transition visible to
already-compiled engines: the next wave retraces and bakes the new
dispatch) and, for wave steps, the mesh — shapes retrace within one
cached entry naturally.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core.bindings import BindingTable
from repro.core.distributed import make_batch_step
from repro.core.server import (
    BRANCH_EVALUATORS,
    EvalCtx,
    UnitPlan,
    eval_unit,
    unit_io,
)
from repro.kernels import ops as kops

_STEP_CACHE: dict[tuple, Callable] = {}


def _branch_statics(up: UnitPlan) -> tuple:
    return tuple((b.case, b.pred_ci, b.subj_src, b.obj_src)
                 for b in up.branches)


def unit_step(up: UnitPlan, radix: int, mesh: Mesh | None = None,
              lane_axes: tuple[str, ...] = (), logn: int | None = None):
    """Jitted one-unit wave step, cached by the unit's trace statics.

    The key holds everything ``eval_unit`` bakes into the trace (branch
    cases, const-vector indices, var columns) plus the dispatch-layer
    FORCE setting read at trace time and the mesh the step lowers onto
    (``None`` for the single-host vmap step); array shapes (cap, n_vars,
    lanes) retrace within one cached step naturally.  ``est_card`` is
    planning metadata and deliberately excluded — same-shaped units from
    different queries share one compilation.

    ``logn`` is the cost model's binary-search factor from the *logical*
    triple count (static — under a delta overlay it can change while
    every shape stays put, so it is part of the key).

    The mesh instantiation replicates the store (``data_axis=None``) and
    splits the wave's lanes across ``lane_axes``, so a lane computes the
    same integer arithmetic it would under vmap — byte-identical outputs,
    different device placement.
    """
    key = ("wave", _branch_statics(up), radix, kops.FORCE,
           kops.BREAKER.generation, mesh, lane_axes, logn)
    step = _STEP_CACHE.get(key)
    if step is None:
        def lane_fn(dev, const_vec, rows, valid, overflow):
            cap = rows.shape[0]
            prov = jnp.arange(cap, dtype=jnp.int32)[:, None]
            table = BindingTable(jnp.concatenate([rows, prov], axis=1),
                                 valid, overflow)
            table, ops, peak = eval_unit(dev, radix, up, const_vec, table,
                                         logn=logn)
            return (table.rows[:, :-1], table.valid, table.overflow,
                    table.rows[:, -1], ops,
                    jnp.sum(table.valid.astype(jnp.int64)), peak)

        if mesh is None:
            step = make_batch_step(lane_fn, name="unit_step")
        else:
            step = make_batch_step(lane_fn, out_proto=(0,) * 7,
                                   mesh=mesh, data_axis=None,
                                   lane_axes=lane_axes, name="unit_step")
        _STEP_CACHE[key] = step
    return step


# --------------------------------------------------------------------------
# sharded-store unit evaluation (the hoisted _lane_eval collective)
# --------------------------------------------------------------------------

# branch cases that only filter (their output count never exceeds their
# input count); every other case is a ragged expansion
_FILTER_CASES = frozenset({"probe_oconst", "probe_ovar_bound"})


def eval_unit_sharded(dev, radix: int, up: UnitPlan, const_vec, table,
                      *, axis: str, logn: int,
                      owner=None, latch_merge=None):
    """One unit's branches against the local store shard, inside shard_map.

    The input ``table`` is replicated along ``axis`` (the lane state is
    merged after every unit).  Because the store is subject-hash sharded
    and all branches of a star share the subject, each row's entire
    evaluation happens on exactly one shard — non-owner shards simply find
    empty runs — so the local branch loop needs *no* collectives and the
    shard-local output tables partition the serial output by subject owner
    (the paper's "server-side star joins never communicate").

    What does need collectives is the *serial cost account*: a scalar
    ``psum`` per branch boundary recovers the global row count, from which
    the exact serial ops/overflow/peak are rebuilt (``engine._execute``'s
    accounting is a pure function of the branch-boundary counts):

        filter     ops += count_in * 3 * logn
        expansion  ops += count_in * 2 * logn + min(total_global, cap)

    with ``logn`` the *global* store's log-factor (the local shard's would
    drift from the serial account) and the expansion's global total the
    psum of local totals.  Overflow is likewise global: an expansion whose
    global total exceeds the lane capacity overflows even when every local
    shard fit — exactly when the serial evaluation would have overflowed,
    so sharded retries fire in lockstep with the serial ladder.

    Returns ``(local_table, ops, peak, count, overflow)`` with ops /
    peak / count / overflow replicated along ``axis`` (built from psums
    and the replicated input) and ``local_table`` the shard-local output
    partition, to be merged by ``gather_merge``.

    ``latch_merge`` is the overflow-latch mode (``cap == max_cap`` waves,
    where a too-big expansion truncates at the capacity in *global* serial
    row order and evaluation continues): a ``(rows, valid) -> (rows,
    valid, lost)`` gather-merge bound to ``trim = out_cap = cap``, run
    after every branch.  The merged-then-truncated table IS the serial
    latch table — a shard's local clamp keeps its local-order prefix,
    which contains the global prefix's restriction to that shard, so
    truncating the merge at ``cap`` reproduces the serial truncation
    exactly.  The replicated result re-partitions on the next branch by
    store locality (probes find runs only on the owning shard, scans
    expand only local runs), and the accounting formulas above are
    latch-exact as-is: ``min(psum(local clamped), cap) ==
    min(global total, cap)`` in every clamp case, and a local clamp the
    count psum can't see still ORs in through the overflow-flag psum.
    When set, the returned table is the *merged, replicated* lane table —
    the caller must not merge again.
    """
    cap = table.cap
    ctx = EvalCtx(dev, radix, const_vec, logn,
                  owner if up.branches[0].case.startswith("probe") else None)
    cnt = table.count()  # replicated input: already the global count
    ops = jnp.int64(0)
    peak = cnt
    over = jnp.asarray(False)
    for b in up.branches:
        table, _ = BRANCH_EVALUATORS[b.case](ctx, b, table)
        cnt_new = jax.lax.psum(table.count(), axis)
        if b.case in _FILTER_CASES:
            ops = ops + cnt * (3 * logn)
        else:
            ops = ops + cnt * (2 * logn) + jnp.minimum(cnt_new, cap)
            over = over | (cnt_new > cap)
        cnt = jnp.minimum(cnt_new, cap)
        peak = jnp.maximum(peak, cnt)
        if latch_merge is not None:
            rows_m, valid_m, _ = latch_merge(table.rows, table.valid)
            table = BindingTable(rows_m, valid_m, table.overflow)
    # local clamps (a shard whose local total exceeded the lane capacity)
    # imply a global clamp, but OR them in explicitly so a lost row can
    # never go unflagged; the input's replicated flag rides along too
    over = over | (jax.lax.psum(table.overflow.astype(jnp.int32), axis) > 0)
    return table, ops, peak, cnt, over


def shard_trim(cap: int, n_shards: int, headroom: int = 2) -> int:
    """Per-shard gather budget for a lane capacity of ``cap``.

    A balanced subject hash puts ~``cap / n_shards`` of any lane's rows on
    each shard, so the per-unit gather ships ``headroom`` times that (skew
    margin) instead of the full capacity per shard — the "per-shard caps =
    planner cap / shards" half of the sharded-mode memory story.  Floored
    at the capacity quantum (``CapacityPlanner.MIN_QUANTUM``): below it
    the gather is overhead-dominated and trimming buys nothing.  A
    shard whose local output exceeds the budget flags overflow and the
    lane retries at 4x — the budget grows with the capacity, so the retry
    converges exactly like a capacity overflow does.  ``n_shards * trim``
    always covers ``cap``, so a fitting result is never truncated.
    """
    from repro.core.capacity import CapacityPlanner

    if n_shards <= 1:
        return cap
    return min(cap, max(headroom * (-(-cap // n_shards)),
                        CapacityPlanner.MIN_QUANTUM))


def lexsort_rows(rows, valid, sort_cols: tuple[int, ...]):
    """Stable lexicographic sort of a row block by ``(~valid, *sort_cols)``
    — valid rows first, then the column keys most-significant-first.
    Returns the sorted ``(rows, valid)``; the replicated-lexsort half of
    the shard merge (``merge_sorted_blocks`` is the other), kept callable
    on its own as the k-way merge's parity baseline and bench foil."""
    n = rows.shape[0]
    perm = jnp.arange(n, dtype=jnp.int32)
    for c in reversed(sort_cols):
        perm = perm[jnp.argsort(rows[:, c][perm], stable=True)]
    perm = perm[jnp.argsort(~valid[perm], stable=True)]
    return rows[perm], valid[perm]


def _merge_keys(rows, valid, sort_cols: tuple[int, ...]):
    """Key columns of a block under the merge order ``(~valid, *sort_cols)``,
    as int32 arrays most-significant-first."""
    return [(~valid).astype(jnp.int32)] + \
        [rows[:, c].astype(jnp.int32) for c in sort_cols]


def _lex_rank_range(keys_s, keys_q):
    """Equal ranges of each query row's key tuple within a block sorted by
    the same key order: per key column, narrow ``[lo, hi)`` by a within-run
    two-sided search (``kops.searchsorted_in_runs`` — the backend-
    dispatched primitive, so the merge rides the kernel seam).  Returns
    ``(lo, hi)`` = (#rows strictly below, #rows at-or-below) per query."""
    n_s = keys_s[0].shape[0]
    n_q = keys_q[0].shape[0]
    lo = jnp.zeros((n_q,), jnp.int32)
    hi = jnp.full((n_q,), n_s, jnp.int32)
    for cs, cq in zip(keys_s, keys_q):
        # left/right insertion of cq within [lo, hi): the block is sorted
        # by this column inside ties of the earlier ones; +1 turns the
        # left search into the right one (int32 keys, far from the max)
        lo_new = kops.searchsorted_in_runs(cs, lo, hi, cq)
        hi = kops.searchsorted_in_runs(cs, lo, hi, cq + 1)
        lo = lo_new
    return lo, hi


def merge_sorted_blocks(rows_a, valid_a, rows_b, valid_b,
                        sort_cols: tuple[int, ...]):
    """Linear merge of two row blocks, each already sorted by
    ``(~valid, *sort_cols)``; block A wins ties (stability).

    The merge is rank-based rather than compare-and-advance: each A row's
    final position is its own index plus the count of strictly-smaller B
    rows, each B row's its index plus the count of at-or-below A rows —
    two vectorized lexicographic rank computations
    (``_lex_rank_range``) and one scatter, no serial loop.  Together the
    positions are a permutation of the output, so the scatter is exact.
    Returns the merged ``(rows, valid)`` of length ``len(A) + len(B)``.
    """
    n_a = rows_a.shape[0]
    n_b, width = rows_b.shape
    keys_a = _merge_keys(rows_a, valid_a, sort_cols)
    keys_b = _merge_keys(rows_b, valid_b, sort_cols)
    b_below_a, _ = _lex_rank_range(keys_b, keys_a)
    _, a_at_or_below_b = _lex_rank_range(keys_a, keys_b)
    pos_a = jnp.arange(n_a, dtype=jnp.int32) + b_below_a
    pos_b = jnp.arange(n_b, dtype=jnp.int32) + a_at_or_below_b
    rows_m = jnp.zeros((n_a + n_b, width), rows_a.dtype)
    rows_m = rows_m.at[pos_a].set(rows_a).at[pos_b].set(rows_b)
    valid_m = jnp.zeros((n_a + n_b,), valid_a.dtype)
    valid_m = valid_m.at[pos_a].set(valid_a).at[pos_b].set(valid_b)
    return rows_m, valid_m


def _trim_block(rows, valid, trim: int):
    """Clip a local block to its gather budget and blank the invalid tail.

    Invalid rows are overwritten with -1 so both merge strategies see (and
    emit) identical bytes outside the valid prefix: all-(-1) rows sorted
    to the back — without this, lexsort and k-way would order the
    invalid-tail garbage differently (harmless downstream, but it would
    reduce "byte-identical" to "byte-identical where it matters").
    Returns ``(rows, valid, lost)`` with ``lost`` = this shard dropped a
    valid row past the trim (shard-local; callers psum/OR it).
    """
    cap = rows.shape[0]
    lost = jnp.asarray(False)
    if trim < cap:
        lost = jnp.any(valid[trim:])
        rows, valid = rows[:trim], valid[:trim]
    return jnp.where(valid[:, None], rows, -1), valid, lost


def _pad_to_cap(rows, valid, out_cap: int, lost):
    width = rows.shape[1]
    n = rows.shape[0]
    if n >= out_cap:
        return rows[:out_cap], valid[:out_cap], lost
    pad = out_cap - n
    return (jnp.concatenate([rows, jnp.full((pad, width), -1, rows.dtype)]),
            jnp.concatenate([valid, jnp.zeros((pad,), valid.dtype)]), lost)


def gather_merge(rows, valid, sort_cols: tuple[int, ...], axis: str,
                 out_cap: int, trim: int):
    """Per-unit collective: gather shard-local outputs and rebuild the lane
    table in *serial row order* (the sharded parity story) — the
    replicated-lexsort strategy: one ``all_gather``, then every device
    sorts the full ``n_shards * trim`` block.

    Each local table holds a partition of the serial output; the serial
    order is recoverable because every output row carries its sort key in
    its own columns: the provenance column (input row index) plus, per
    expansion branch in branch order, the value that branch drew — runs
    are sorted by exactly those values in the store layout, and expansions
    refine the order of their source rows, so the lexicographic sort by
    ``sort_cols`` over the gathered rows reproduces the serial table
    byte-for-byte.  Keys are unique among valid rows (triples are a set,
    and a subject lives on one shard), so the order is total regardless of
    how shard blocks interleave.

    ``trim`` bounds the per-shard contribution (``shard_trim``); locally
    compacted tables lose only rows past the trim, and ``lost`` reports
    whether THIS shard dropped a valid row.  ``lost`` is shard-local —
    unlike the merged rows/valid (replicated by the all_gather), it must
    be psum/OR-reduced over ``axis`` before use, which is what both
    callers do when folding it into the lane overflow flag.
    Returns ``(rows[out_cap], valid[out_cap], lost)``.

    ``gather_merge_kway`` is the k-way strategy with the same contract and
    bit-identical outputs; ``select_gather_merge`` picks between them.
    """
    rows, valid, lost = _trim_block(rows, valid, trim)
    trim = rows.shape[0]
    width = rows.shape[1]
    rows_g = jax.lax.all_gather(rows, axis)
    n_shards = rows_g.shape[0]  # static, from the gathered leading axis
    rows_g = rows_g.reshape(n_shards * trim, width)
    valid_g = jax.lax.all_gather(valid, axis).reshape(n_shards * trim)
    rows_m, valid_m = lexsort_rows(rows_g, valid_g, sort_cols)
    return _pad_to_cap(rows_m, valid_m, out_cap, lost)


def gather_merge_kway(rows, valid, sort_cols: tuple[int, ...], axis: str,
                      out_cap: int, trim: int, n_shards: int):
    """``gather_merge`` as a k-way merge over pre-sorted shard blocks.

    Every shard's local block is already in serial order (the valid prefix
    is the serial table restricted to the shard; the blanked invalid tail
    is a run of -1 rows), so the replicated ``n_shards * trim`` lexsort is
    redundant work: ``log2(n_shards)`` recursive-doubling rounds of
    pairwise ``merge_sorted_blocks`` — partner exchange via
    ``ppermute(j <-> j ^ 2**r)``, lower-index block on the left — leave
    every device holding the fully merged block, replicated exactly like
    the all_gather result.  Each round is linear-plus-rank work instead of
    a full sort, and the rank computations ride the dispatched
    ``searchsorted_in_runs`` primitive (Pallas on TPU).

    Non-power-of-two shard counts run a padded schedule around the
    power-of-two core (``base = 2**floor(log2 n)``, ``rem = n - base``):
    a pre-round folds each extra block ``base+i`` into device ``i``
    (``ppermute`` delivers zeros to non-recipients, which are re-blanked
    to the invalid -1 encoding before the merge, so non-folding devices
    just merge with an empty block); the main rounds run recursive
    doubling among devices ``0..base-1`` only; a post-round broadcasts
    the merged block back onto the extras.  Naive in-place phantom
    padding would be wrong — recursive doubling relies on every partner
    holding its whole subgroup's merge, which phantom partners break —
    hence the fold/broadcast bracket (the classic MPI reduction schedule
    for non-power-of-two communicators).

    Same contract and bit-identical outputs as ``gather_merge`` (pinned by
    the shard-merge parity tests) at every shard count.
    """
    rows, valid, lost = _trim_block(rows, valid, trim)
    if n_shards <= 1:
        return _pad_to_cap(rows, valid, out_cap, lost)
    idx = jax.lax.axis_index(axis)
    base = 1 << (n_shards.bit_length() - 1)
    rem = n_shards - base
    if rem:
        # pre-round: fold extra blocks into the pow2 core (base+i -> i)
        perm = [(base + i, i) for i in range(rem)]
        o_rows = jax.lax.ppermute(rows, axis, perm)
        o_valid = jax.lax.ppermute(valid, axis, perm)
        o_rows = jnp.where(o_valid[:, None], o_rows, -1)
        rows, valid = merge_sorted_blocks(rows, valid, o_rows, o_valid,
                                          sort_cols)
    for r in range(base.bit_length() - 1):
        d = 1 << r
        perm = [(j, j ^ d) for j in range(base)]
        o_rows = jax.lax.ppermute(rows, axis, perm)
        o_valid = jax.lax.ppermute(valid, axis, perm)
        if rem:
            # extras sit the core rounds out: the zeros they receive must
            # read as invalid rows, not as key value 0
            o_rows = jnp.where(o_valid[:, None], o_rows, -1)
        am_left = (idx & d) == 0
        rows_a = jnp.where(am_left, rows, o_rows)
        rows_b = jnp.where(am_left, o_rows, rows)
        valid_a = jnp.where(am_left, valid, o_valid)
        valid_b = jnp.where(am_left, o_valid, valid)
        rows, valid = merge_sorted_blocks(rows_a, valid_a, rows_b, valid_b,
                                          sort_cols)
    if rem:
        # post-round: replicate the merged block back onto the extras
        perm = [(i, base + i) for i in range(rem)]
        o_rows = jax.lax.ppermute(rows, axis, perm)
        o_valid = jax.lax.ppermute(valid, axis, perm)
        is_extra = idx >= base
        rows = jnp.where(is_extra, o_rows, rows)
        valid = jnp.where(is_extra, o_valid, valid)
    return _pad_to_cap(rows, valid, out_cap, lost)


def select_gather_merge(merge: str, n_shards: int):
    """Resolve a merge policy name to a gather-merge callable with the
    ``gather_merge`` signature.  ``"auto"`` takes the k-way merge at every
    shard count (non-power-of-two counts run its padded fold/broadcast
    schedule); ``"lexsort"`` is the only remaining fallback — explicit
    opt-in, counted per sharded step in
    ``SchedMetrics.merge_lexsort_steps``.  Outputs are bit-identical
    either way — the policy is pure placement of the merge work."""
    if merge == "lexsort":
        return gather_merge
    if merge not in ("auto", "kway"):
        raise ValueError(f"merge must be 'auto', 'kway' or 'lexsort'; "
                         f"got {merge!r}")
    return partial(_kway_with_shards, n_shards=n_shards)


def _kway_with_shards(rows, valid, sort_cols, axis, out_cap, trim, *,
                      n_shards):
    return gather_merge_kway(rows, valid, sort_cols, axis, out_cap, trim,
                             n_shards)


def sharded_unit_step(up: UnitPlan, radix: int, mesh: Mesh, data_axis: str,
                      lane_axes: tuple[str, ...], n_shards: int, logn: int,
                      trim: int, latch: bool = False, merge: str = "auto"):
    """Jitted one-unit wave step over a subject-hash sharded store.

    The third instantiation of the shared lane evaluator (vmap /
    replicated shard_map / THIS): the store carries a leading shard axis
    split along ``data_axis``, wave lanes split along ``lane_axes``, and
    each unit step is local branch evaluation + an order-restoring
    collective (``eval_unit_sharded`` + ``select_gather_merge``) — the
    same per-unit collective ``DistributedEngine``'s whole-query lane
    evaluator uses, hoisted into the step machinery.  Outputs extend
    ``unit_step``'s 7-tuple and are byte-identical to it on those seven:
    same rows in the same order, same ops/count/peak (exact via scalar
    psums), same overflow flag (derived from global totals); the eighth
    output is ``shard_peak`` — the pmax over shards of the largest local
    pre-merge row count any branch produced, which the scheduler feeds
    back into the next wave's ``trim`` (occupancy-fed gather budgets via
    ``CapacityPlanner.observe_shard_peak``).  ``logn`` is the *global*
    store's log-factor (static — shapes inside the step only see the
    shard).

    ``trim`` is the static per-shard gather budget (``shard_trim`` cold,
    an observed-peak hint warm); ``latch = True`` is the overflow-latch
    rung (``cap == max_cap``): the merge runs after *every* branch at
    ``trim = cap`` so mid-unit truncation happens in global serial row
    order — what used to force latch waves onto the replicated/vmap
    lowerings.  ``merge`` picks the gather-merge strategy
    (``select_gather_merge``).
    """
    key = ("shard", _branch_statics(up), radix, kops.FORCE,
           kops.BREAKER.generation, mesh,
           data_axis, lane_axes, n_shards, logn, trim, latch, merge)
    step = _STEP_CACHE.get(key)
    if step is None:
        io = unit_io(up)
        write_cols = tuple(io.write_cols)
        merge_fn = select_gather_merge(merge, n_shards)

        def lane_fn(dev, const_vec, rows, valid, overflow):
            cap, n_vars = rows.shape
            prov = jnp.arange(cap, dtype=jnp.int32)[:, None]
            table = BindingTable(jnp.concatenate([rows, prov], axis=1),
                                 valid, overflow)
            # serial order: provenance first, then each expansion branch's
            # drawn value(s) — write_cols is exactly those, in branch
            # order.  Valid mid-unit too: unwritten columns are uniformly
            # UNBOUND, so they never perturb an earlier merge's order.
            sort_cols = (n_vars,) + write_cols
            latch_merge = None
            if latch:
                def latch_merge(r, v):
                    return merge_fn(r, v, sort_cols, data_axis, cap, cap)
            table, ops, peak, cnt, ovf = eval_unit_sharded(
                dev, radix, up, const_vec, table, axis=data_axis, logn=logn,
                latch_merge=latch_merge)
            # the trim budget the NEXT wave of this unit actually needs:
            # the biggest local block any shard tried to ship
            shard_peak = jax.lax.pmax(table.count().astype(jnp.int32),
                                      data_axis)
            if latch:
                rows_m, valid_m = table.rows, table.valid  # already merged
            else:
                rows_m, valid_m, lost = merge_fn(
                    table.rows, table.valid, sort_cols, data_axis, cap,
                    min(trim, cap))
                ovf = ovf | (jax.lax.psum(lost.astype(jnp.int32),
                                          data_axis) > 0)
            return (rows_m[:, :-1], valid_m, ovf, rows_m[:, -1], ops, cnt,
                    peak, shard_peak)

        step = make_batch_step(lane_fn, out_proto=(0,) * 8, mesh=mesh,
                               data_axis=data_axis, lane_axes=lane_axes,
                               name="sharded_unit_step")
        _STEP_CACHE[key] = step
    return step


def serial_unit_step(up: UnitPlan, radix: int, logn: int | None = None):
    """The serial engine's ladder step: ``unit_step`` without the
    provenance column (``run`` checkpoints tables, not cache deltas).
    Batched with a leading lane axis like every ``make_batch_step``
    product — the engine passes a width-1 batch.  ``logn`` carries the
    logical-count cost factor (see ``unit_step``)."""
    key = ("serial", _branch_statics(up), radix, kops.FORCE,
           kops.BREAKER.generation, logn)
    step = _STEP_CACHE.get(key)
    if step is None:
        def lane_fn(dev, const_vec, rows, valid, overflow):
            table, ops, peak = eval_unit(dev, radix, up, const_vec,
                                         BindingTable(rows, valid, overflow),
                                         logn=logn)
            return (table.rows, table.valid, table.overflow, ops,
                    jnp.sum(table.valid.astype(jnp.int64)), peak)

        step = make_batch_step(lane_fn, name="serial_unit_step")
        _STEP_CACHE[key] = step
    return step


def _on_lanes(fn: Callable, n_args: int, n_out: int, mesh: Mesh | None,
              lane_axes: tuple[str, ...]) -> Callable:
    """``fn`` over a wave's lanes, jitted.  A mesh wave's table comes back
    from its unit step with the lanes split along ``lane_axes`` and
    replicated over the other mesh axes; ``fn`` then runs per device in
    that layout, inside ``shard_map``: XLA cannot partition a Mosaic
    kernel by itself."""
    if mesh is None:
        return jax.jit(fn)
    spec = P() if not lane_axes else \
        P(lane_axes if len(lane_axes) > 1 else lane_axes[0])
    return jax.jit(shard_map(fn, mesh, (spec,) * n_args,
                             spec if n_out == 1 else (spec,) * n_out))


def digest_step(read_cols: tuple[int, ...], mesh: Mesh | None = None,
                lane_axes: tuple[str, ...] = ()):
    """Jitted wave fingerprint: ``(rows[B, cap, V], valid[B, cap]) ->
    uint32[B, 4]`` digests of each lane's valid prefix restricted to
    ``read_cols`` — the device half of the digest-first cache keys
    (host twin: ``ref.fingerprint_prefix_np`` on replayed state).
    ``mesh``/``lane_axes``: the lane layout of a mesh wave (see
    ``_on_lanes``)."""
    key = ("digest", read_cols, kops.FORCE, kops.BREAKER.generation, mesh,
           lane_axes)
    fn = _STEP_CACHE.get(key)
    if fn is None:
        cols = jnp.asarray(read_cols, jnp.int32) if read_cols else None

        def wave_digest(rows, valid):
            block = jnp.take(rows, cols, axis=2) if cols is not None \
                else rows[:, :, :0]
            return jax.vmap(kops.fingerprint_rows)(block, valid)

        fn = _STEP_CACHE[key] = _on_lanes(wave_digest, 2, 1, mesh,
                                          lane_axes)
    return fn


def replay_step(write_cols: tuple[int, ...], mesh: Mesh | None = None,
                lane_axes: tuple[str, ...] = ()):
    """Jitted wave-wide device-side cache-hit replay.

    ``(rows[B, cap, V], src[B, M], written[B, M, W], n_out[B]) ->
    (rows[B, cap, V], valid[B, cap])``: every lane's cached fragment delta
    is scattered onto its seed prefix in place (``kops.replay_delta`` —
    Pallas broadcast-compare gather on TPU, jnp oracle elsewhere, numpy
    twin ``fragcache.replay``).  Lanes with ``n_out == 0`` (padding,
    retired, negative fragments) come out empty.  This is what keeps
    all-hit waves off the host: the uploaded delta is the small object,
    the Omega block never moves.  ``mesh``/``lane_axes``: the lane layout
    of a mesh wave (see ``_on_lanes``).
    """
    key = ("replay", tuple(write_cols), kops.FORCE,
           kops.BREAKER.generation, mesh, lane_axes)
    fn = _STEP_CACHE.get(key)
    if fn is None:
        cols = tuple(write_cols)

        def wave_replay(rows, src, written, n_out):
            return jax.vmap(
                lambda r, s, w, n: kops.replay_delta(r, s, w, n, cols)
            )(rows, src, written, n_out)

        fn = _STEP_CACHE[key] = _on_lanes(wave_replay, 4, 2, mesh,
                                          lane_axes)
    return fn


@partial(jax.jit, static_argnames=("new_cap",))
def reseat(rows: jnp.ndarray, valid: jnp.ndarray, new_cap: int):
    """Re-home a compacted table at a new capacity.

    Growing pads the tail with UNBOUND rows; shrinking drops tail rows
    (callers guarantee the valid prefix fits — planner rungs always cover
    the seed row count).  The valid prefix is preserved bit-for-bit, which
    is what makes re-entering the ladder at the overflowed unit
    byte-identical to the blind whole-query retry.
    """
    cap, n_vars = rows.shape
    if new_cap <= cap:
        return rows[:new_cap], valid[:new_cap]
    pad = new_cap - cap
    return (jnp.concatenate(
                [rows, jnp.full((pad, n_vars), -1, rows.dtype)]),
            jnp.concatenate([valid, jnp.zeros((pad,), valid.dtype)]))


def endpoint_totals(cfg, n_results: int, n_vars: int) -> tuple[int, int]:
    """(nrs, ntb) of a whole endpoint-interface query — the host twin of
    ``engine._execute``'s end-of-query override (one request, the full
    result set in one response).  Shared by the planned serial path and
    the scheduler finalize so the three copies cannot drift to two."""
    return (1, cfg.request_base_bytes + n_results * n_vars * cfg.term_bytes
            + cfg.page_header_bytes)


def unit_cost(cfg, k: int, up: UnitPlan, in_count: int, out_count: int,
              ops: int, probe_ops: int) -> tuple[int, int, int, int]:
    """(nrs, ntb, server_ops, client_ops) deltas for one unit, in ints.

    Mirrors the traced accounting in ``engine._execute`` exactly; the
    scheduler/serial stats-parity tests pin the two together.  ``k`` is
    the unit's absolute position in the plan (resumed executions keep
    their original indices).  ``probe_ops`` is the dispatched per-probe
    cost of the TPF fragment-location path (``kops.probe_op_cost`` — the
    active kernel's model, not an analytic logn), unused by the other
    interfaces.
    """
    tb = cfg.term_bytes
    matched = out_count * up.n_triple_patterns
    if cfg.interface == "endpoint":
        return 0, 0, ops, 0
    meta = 1
    if cfg.interface == "tpf":
        blocks = max(in_count, 1) if k > 0 else 1
    else:  # brtpf / spf: Omega-blocked requests
        blocks = -(-max(in_count, 1) // cfg.omega) if k > 0 else 1
    pages = -(-max(out_count, 1) // cfg.page_size)
    extra = max(pages - blocks, 0)
    nrs_d = meta + blocks + extra
    sent = (blocks + meta + extra) * cfg.request_base_bytes
    if cfg.interface in ("brtpf", "spf") and k > 0:
        n_bound_vars = len(
            {v for b in up.branches for src in (b.subj_src, b.obj_src)
             if src[0] == "var" for v in [src[1]]})
        sent += in_count * max(n_bound_vars, 1) * tb
    recv = matched * 3 * tb + (pages + meta) * cfg.page_header_bytes
    ntb_d = sent + recv
    if cfg.interface == "tpf":
        server_d = blocks * probe_ops + matched
        client_d = ops
    else:
        server_d = ops
        client_d = out_count
    return nrs_d, ntb_d, server_d, client_d
