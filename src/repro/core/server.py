"""Server-side fragment evaluation: planning + traced star/TP evaluation.

A *unit* is what one request evaluates — a star pattern for the SPF
interface, a single triple pattern for TPF/brTPF (a 1-branch star).  The
evaluator is *seeded*: it receives the current table of solution mappings
(the paper's Omega) and extends/filters it, which is exactly bind-join /
bindings-restricted semantics (Def. 5, non-empty-Omega case).

Planning is host-side and uses exact run lengths from the store's numpy
indexes (= the Def. 6 cardinality metadata, eps = 0); evaluation is traced
JAX over the device indexes.  Query *structure* (the case-tag sequence) is
static; every constant id is routed through a traced ``const_vec`` so that
structurally identical queries share one XLA compilation.

Branch cases (derived at plan time from the bound-variable set):

    probe_oconst      subject bound, object const          -> filter
    probe_ovar_bound  subject bound, object var bound      -> filter
    probe_ovar_free   subject bound, object var free       -> expand objects
    scan_oconst       subject free,  object const          -> expand subjects (POS run)
    scan_ovar_bound   subject free,  object var bound      -> expand subjects (POS eqrange)
    scan_ovar_free    subject free,  object var free       -> expand pred run (PSO)

Each case is one small evaluator in ``BRANCH_EVALUATORS``; ``eval_unit``
just walks the plan and dispatches.  Every probe/membership primitive the
evaluators touch routes through the backend-dispatched kernel layer
``repro.kernels.ops`` (Pallas on TPU, jnp oracles elsewhere, ``ops.FORCE``
override) — this module contains no searchsorted/bisection of its own.
The evaluators are traced inside jit here, and inside shard_map+vmap by
``core/distributed.py``; the dispatched primitives are safe under both.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core.bindings import (
    BindingTable,
    Expansion,
    compact,
    expand,
    source_rows,
)
from repro.core.patterns import StarPattern
from repro.kernels import ops as kops
from repro.rdf.store import StoreArrays, TripleStore


# --------------------------------------------------------------------------
# plans (host-side, static)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class BranchPlan:
    case: str  # one of the six tags above
    pred_ci: int  # index into const_vec (predicate id)
    subj_src: tuple[str, int]  # ("var", var_idx) | ("const", const_vec idx)
    obj_src: tuple[str, int]  # ("var", var_idx) | ("const", const_vec idx)
    est_card: int  # host-side run length (planning metadata)


@dataclass(frozen=True)
class UnitPlan:
    branches: tuple[BranchPlan, ...]
    est_card: int  # Def. 6 metadata estimate for the whole unit
    n_triple_patterns: int

    @property
    def signature(self) -> tuple:
        """Compile-sharing key: case structure without constant values."""
        return tuple((b.case, b.subj_src[0], b.obj_src[0],
                      b.subj_src[1] if b.subj_src[0] == "var" else -1,
                      b.obj_src[1] if b.obj_src[0] == "var" else -1)
                     for b in self.branches)


def plan_unit(store: TripleStore, star: StarPattern, bound: frozenset[int],
              consts: list[int]) -> tuple[UnitPlan, frozenset[int]]:
    """Plan one unit given the currently bound variable set.

    Branch order: most selective first (smallest host cardinality), with the
    constraint that once the subject is bound all remaining branches become
    probes.  Returns the plan and the updated bound set.
    """
    if not star.subject.is_var and star.subject.id is None:
        raise ValueError("invalid subject term")

    def add_const(cid: int) -> int:
        consts.append(int(cid))
        return len(consts) - 1

    # host cardinalities per branch (before binding anything)
    infos = []
    for p_term, o_term in star.branches:
        if p_term.is_var:
            raise NotImplementedError(
                "unbound-predicate patterns are outside the WatDiv loads; "
                "the SPF server would fall back to a full scan")
        p = p_term.id
        if not o_term.is_var:
            card = store.tp_cardinality(p, o=o_term.id)
        else:
            card = store.tp_cardinality(p)
        infos.append((card, p_term, o_term))
    # selective-first ordering; const-object branches are naturally smallest
    infos.sort(key=lambda t: t[0])

    subj = star.subject
    subj_bound = (not subj.is_var) or (subj.id in bound)
    new_bound = set(bound)
    branches: list[BranchPlan] = []
    for card, p_term, o_term in infos:
        p_ci = add_const(p_term.id)
        subj_src = ("const", add_const(subj.id)) if not subj.is_var else ("var", subj.id)
        if not o_term.is_var:
            obj_src = ("const", add_const(o_term.id))
            case = "probe_oconst" if subj_bound else "scan_oconst"
        elif o_term.id in new_bound:
            obj_src = ("var", o_term.id)
            case = "probe_ovar_bound" if subj_bound else "scan_ovar_bound"
        else:
            obj_src = ("var", o_term.id)
            case = "probe_ovar_free" if subj_bound else "scan_ovar_free"
            new_bound.add(o_term.id)
        branches.append(BranchPlan(case, p_ci, subj_src, obj_src, card))
        if not subj_bound:
            subj_bound = True
            if subj.is_var:
                new_bound.add(subj.id)

    est = min(i[0] for i in infos)
    return (UnitPlan(tuple(branches), est, len(star.branches)),
            frozenset(new_bound))


# --------------------------------------------------------------------------
# traced evaluation: one small evaluator per branch case
# --------------------------------------------------------------------------

class EvalCtx(NamedTuple):
    """Static-per-unit evaluation context shared by the branch evaluators."""

    dev: StoreArrays
    radix: int
    const_vec: jnp.ndarray
    logn: int  # ceil(log2 n): the cost model's binary-search factor
    # distributed owner masking: (my_shard, n_shards) on a subject-hash
    # sharded store, None on a single-host store.  When set, bound-subject
    # probes dispatch through ``kops.eqrange_owned`` — non-owned rows get
    # empty runs inside the probe instead of a separate mask pass.
    owner: tuple[jnp.ndarray, int] | None = None


# evaluator signature: (ctx, branch, table) -> (table, ops_delta)
BranchEvaluator = Callable[[EvalCtx, BranchPlan, BindingTable],
                           tuple[BindingTable, jnp.ndarray]]


def _term_values(rows: jnp.ndarray, src: tuple[str, int],
                 const_vec: jnp.ndarray) -> jnp.ndarray:
    kind, idx = src
    if kind == "const":
        return jnp.broadcast_to(const_vec[idx].astype(jnp.int32),
                                (rows.shape[0],))
    return rows[:, idx]


def _composite_key(ctx: EvalCtx, b: BranchPlan,
                   term: jnp.ndarray) -> jnp.ndarray:
    """int32 probe key ``p * radix + term`` (the store's key layout;
    ``rdf.store.check_key_radix`` keeps it inside int32)."""
    return ctx.const_vec[b.pred_ci].astype(jnp.int32) * ctx.radix + term


def _active(table: BindingTable) -> jnp.ndarray:
    return jnp.sum(table.valid.astype(jnp.int64))


def _has_delta(dev: StoreArrays) -> bool:
    """Trace-time static: is a delta overlaid on the base index?

    Shapes are static under jit, so each branch evaluator specialises at
    trace time — with an empty delta the emitted computation is exactly
    the pre-delta one (no delta probes, no merge), and a delta-bearing
    epoch simply retraces (the scheduler's step-cache keys fold the
    epoch/shapes).
    """
    return dev.ins_key_ps.shape[0] > 0 or dev.tomb_pos_ps.shape[0] > 0


def _probe_run(ctx: EvalCtx, b: BranchPlan, table: BindingTable
               ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray | None,
                          jnp.ndarray]:
    """Locate each row's ``(p, s)`` run in PSO order (bound-subject cases).

    Returns ``(lo, hi, owned, key)``; ``owned`` is None on a single-host
    store and the per-row ownership mask under distributed owner masking
    (where non-owned rows already carry an empty run).  ``key`` is the
    composite probe key — the delta overlay probes the same key into the
    insert column.  On a sharded store the shard's delta holds only owned
    triples, so a non-owned row's key misses the insert run too — owner
    masking needs no delta-side mask pass."""
    s_vals = _term_values(table.rows, b.subj_src, ctx.const_vec)
    key = _composite_key(ctx, b, s_vals)
    if ctx.owner is None:
        lo, hi = kops.eqrange(ctx.dev.key_ps_pso, key)
        return lo, hi, None, key
    my_shard, n_shards = ctx.owner
    lo, hi, owned = kops.eqrange_owned(ctx.dev.key_ps_pso, key, s_vals,
                                       my_shard, n_shards)
    return lo, hi, owned, key


def _probe_active(table: BindingTable, owned: jnp.ndarray | None
                  ) -> jnp.ndarray:
    """Rows the local store actually probes (owner-masked when sharded)."""
    valid = table.valid if owned is None else table.valid & owned
    return jnp.sum(valid.astype(jnp.int64))


def _expand_into(ctx: EvalCtx, b: BranchPlan, table: BindingTable,
                 ex: Expansion, subj_from: jnp.ndarray | None,
                 obj_from: jnp.ndarray | None
                 ) -> tuple[BindingTable, jnp.ndarray]:
    """Materialise an expansion into a fresh table, filling var columns
    from the given store columns; returns (table, expansion ops)."""
    new_rows = table.rows[ex.src_row]
    if subj_from is not None and b.subj_src[0] == "var":
        new_rows = new_rows.at[:, b.subj_src[1]].set(
            subj_from[ex.flat_idx].astype(jnp.int32))
    if obj_from is not None:
        new_rows = new_rows.at[:, b.obj_src[1]].set(
            obj_from[ex.flat_idx].astype(jnp.int32))
    overflow = table.overflow | (ex.total > table.cap)
    return (BindingTable(new_rows, ex.valid, overflow),
            jnp.minimum(ex.total, table.cap))


def _run_rank(col: jnp.ndarray, rlo: jnp.ndarray, rhi: jnp.ndarray,
              x0: jnp.ndarray, col2: jnp.ndarray | None = None,
              x1: jnp.ndarray | None = None) -> jnp.ndarray:
    """Absolute "left" rank of value ``x0`` (or pair ``(x0, x1)`` under
    ``(col, col2)`` lex order) within each sorted run ``col[rlo:rhi)``.

    The pair rank needs no right-sided search: ids are integers, so the
    left rank of ``x0 + 1`` *is* the right rank of ``x0`` (the same trick
    as ``stepper._lex_rank_range``)."""
    a = kops.searchsorted_in_runs(col, rlo, rhi, x0)
    if col2 is None:
        return a
    b = kops.searchsorted_in_runs(col, rlo, rhi, x0 + 1)
    return kops.searchsorted_in_runs(col2, a, b, x1)


def _merged_expand(ctx: EvalCtx, table: BindingTable, lo: jnp.ndarray,
                   hi: jnp.ndarray, dprobe: tuple, order: str,
                   cols: tuple) -> tuple[list, jnp.ndarray, jnp.ndarray,
                                         jnp.ndarray]:
    """Ragged expansion over *merged* base+delta runs, in sorted order.

    ``lo``/``hi`` are each row's base run, ``dprobe`` the fused
    ``kops.delta_probe`` result ``(ins_lo, ins_hi, tomb_lo, tomb_hi)``
    for the same rows, ``order`` picks the tombstone arrays ("ps"/"po"),
    and ``cols`` is a tuple of ``(base_col, ins_col)`` pairs in lex
    significance order (one pair for single-column expansions, two for
    the (s, o) pair expansion of ``scan_ovar_free``).

    Rather than materialising and sorting the union, both sides scatter
    directly to their merged ranks (rank-and-scatter):

    - a **live base** element with in-row live rank ``r`` maps to base
      position ``k + rank_right(tomb_adj, k)`` where ``k`` is its global
      live index (``lo - tomb_lo + r``) — the tombstone-select closed
      form — and lands at merged rank ``r`` + (inserts below its value);
    - an **insert** element with in-row rank ``r`` lands at merged rank
      ``r`` + (live base elements below its value), where "live below" is
      (base rank below) − (tombstones below).

    Each side enumerates ``cap`` output slots; a side's scatter position
    is always >= its enumeration index (the other side only pushes ranks
    up), so every merged output slot below ``cap`` is covered — first-cap
    truncation semantics identical to a rebuilt store's plain expansion.
    Values are unique within a run (triple sets; inserts are disjoint
    from the live base), so scatter positions never collide.  Out-of-cap
    positions drop (explicit ``mode="drop"`` — the jit default silently
    *clips*, which would corrupt the last row).

    Returns ``(vals, src_row, valid, total)`` with one gathered value
    array per entry of ``cols``.
    """
    dev = ctx.dev
    tomb_pos = dev.tomb_pos_ps if order == "ps" else dev.tomb_pos_po
    tomb_adj = dev.tomb_adj_ps if order == "ps" else dev.tomb_adj_po
    ilo, ihi, tlo, thi = dprobe
    m = cols[0][1].shape[0]  # inserts (static)
    t = tomb_pos.shape[0]  # tombstones (static)
    cap = table.cap
    n_rows = lo.shape[0]
    nb = cols[0][0].shape[0]

    deg_live = jnp.where(table.valid,
                         ((hi - lo) - (thi - tlo)).astype(jnp.int64), 0)
    deg_ins = jnp.where(table.valid, (ihi - ilo).astype(jnp.int64), 0)
    deg = deg_live + deg_ins
    cum = jnp.cumsum(deg)
    total = cum[-1]
    starts = cum - deg
    j = jnp.arange(cap, dtype=jnp.int64)

    vals = [jnp.zeros((cap,), jnp.int32) for _ in cols]
    src_out = jnp.zeros((cap,), jnp.int32)

    # side A: live base elements
    cum_l = jnp.cumsum(deg_live)
    starts_l = cum_l - deg_live
    src_a = jnp.clip(source_rows(cum_l, cap), 0, n_rows - 1)
    r_a = j - starts_l[src_a]
    valid_a = j < cum_l[-1]
    k_glob = (lo[src_a] - tlo[src_a]).astype(jnp.int64) + r_a
    k_glob = jnp.clip(k_glob, 0, max(nb - 1, 0))
    if t:
        q = k_glob + kops.searchsorted(
            tomb_adj, k_glob.astype(jnp.int32), side="right")
    else:
        q = k_glob
    q = jnp.clip(q, 0, max(nb - 1, 0))
    xs = [bc[q] for bc, _ in cols]
    if m:
        if len(cols) == 1:
            c_a = _run_rank(cols[0][1], ilo[src_a], ihi[src_a], xs[0])
        else:
            c_a = _run_rank(cols[0][1], ilo[src_a], ihi[src_a], xs[0],
                            cols[1][1], xs[1])
        ins_below = (c_a - ilo[src_a]).astype(jnp.int64)
    else:
        ins_below = jnp.int64(0)
    pos_a = jnp.where(valid_a, starts[src_a] + r_a + ins_below, cap)
    for i, x in enumerate(xs):
        vals[i] = vals[i].at[pos_a].set(x.astype(jnp.int32), mode="drop")
    src_out = src_out.at[pos_a].set(src_a.astype(jnp.int32), mode="drop")

    # side B: insert elements
    if m:
        cum_i = jnp.cumsum(deg_ins)
        starts_i = cum_i - deg_ins
        src_b = jnp.clip(source_rows(cum_i, cap), 0, n_rows - 1)
        r_b = j - starts_i[src_b]
        valid_b = j < cum_i[-1]
        flat = jnp.clip(ilo[src_b].astype(jnp.int64) + r_b, 0, m - 1)
        bs = [ic[flat] for _, ic in cols]
        if len(cols) == 1:
            p_b = _run_rank(cols[0][0], lo[src_b], hi[src_b], bs[0])
        else:
            p_b = _run_rank(cols[0][0], lo[src_b], hi[src_b], bs[0],
                            cols[1][0], bs[1])
        below = (p_b - lo[src_b]).astype(jnp.int64)
        if t:
            below = below - (kops.searchsorted(tomb_pos, p_b, side="left")
                             - tlo[src_b]).astype(jnp.int64)
        pos_b = jnp.where(valid_b, starts[src_b] + r_b + below, cap)
        for i, x in enumerate(bs):
            vals[i] = vals[i].at[pos_b].set(x.astype(jnp.int32),
                                            mode="drop")
        src_out = src_out.at[pos_b].set(src_b.astype(jnp.int32),
                                        mode="drop")

    return vals, src_out, j < total, total


def _merged_into(ctx: EvalCtx, b: BranchPlan, table: BindingTable,
                 lo: jnp.ndarray, hi: jnp.ndarray, dprobe: tuple,
                 order: str, cols: tuple, write_subj: bool,
                 write_obj: bool) -> tuple[BindingTable, jnp.ndarray]:
    """Materialise a merged expansion into a fresh table (the delta-path
    twin of ``_expand_into``); returns (table, expansion ops)."""
    vals, src, valid, total = _merged_expand(ctx, table, lo, hi, dprobe,
                                             order, cols)
    new_rows = table.rows[src]
    ci = 0
    if write_subj and b.subj_src[0] == "var":
        new_rows = new_rows.at[:, b.subj_src[1]].set(vals[ci])
        ci += 1
    if write_obj:
        new_rows = new_rows.at[:, b.obj_src[1]].set(vals[ci])
    overflow = table.overflow | (total > table.cap)
    return (BindingTable(new_rows, valid, overflow),
            jnp.minimum(total, table.cap))


def probe_filter(ctx: EvalCtx, b: BranchPlan, table: BindingTable
                 ) -> tuple[BindingTable, jnp.ndarray]:
    """probe_oconst / probe_ovar_bound: subject and object both bound —
    a pure bind-join membership filter over the (p, s) runs.  Under owner
    masking non-owned rows carry empty runs, so membership is False for
    them with no extra mask pass.

    Delta overlay: a base hit only counts if its position is not
    tombstoned, and the insert run can supply the hit instead — the
    merged membership ``(base & ~tomb) | ins``.
    """
    lo, hi, owned, key = _probe_run(ctx, b, table)
    active = _probe_active(table, owned)
    o_vals = _term_values(table.rows, b.obj_src, ctx.const_vec)
    delta = active * (2 * ctx.logn) + active * ctx.logn
    if not _has_delta(ctx.dev):
        found = kops.run_contains(ctx.dev.o_pso, lo, hi, o_vals)
    else:
        pos, found = kops.run_probe(ctx.dev.o_pso, lo, hi, o_vals)
        if ctx.dev.tomb_pos_ps.shape[0]:
            _, t_hit = kops.sorted_probe(ctx.dev.tomb_pos_ps, pos)
            found = found & ~t_hit
        if ctx.dev.ins_key_ps.shape[0]:
            ilo, ihi, _, _ = kops.delta_probe(
                ctx.dev.ins_key_ps, ctx.dev.tomb_pos_ps, key, lo, hi)
            found = found | kops.run_contains(ctx.dev.ins_o_pso, ilo, ihi,
                                              o_vals)
    return compact(BindingTable(table.rows, table.valid & found,
                                table.overflow)), delta


def probe_ovar_free(ctx: EvalCtx, b: BranchPlan, table: BindingTable
                    ) -> tuple[BindingTable, jnp.ndarray]:
    """Subject bound, object free: expand objects within each (p, s) run.
    Non-owned rows (empty runs) contribute zero expansion degree.  With a
    delta, the expansion runs over the merged live-base + insert runs."""
    lo, hi, owned, key = _probe_run(ctx, b, table)
    active = _probe_active(table, owned)
    if not _has_delta(ctx.dev):
        ex = expand(lo, hi, table.valid, table.cap)
        out, ex_ops = _expand_into(ctx, b, table, ex, None, ctx.dev.o_pso)
        return out, active * (2 * ctx.logn) + ex_ops
    dp = kops.delta_probe(ctx.dev.ins_key_ps, ctx.dev.tomb_pos_ps, key,
                          lo, hi)
    out, ex_ops = _merged_into(
        ctx, b, table, lo, hi, dp, "ps",
        ((ctx.dev.o_pso, ctx.dev.ins_o_pso),), False, True)
    return out, active * (2 * ctx.logn) + ex_ops


def scan_obound(ctx: EvalCtx, b: BranchPlan, table: BindingTable
                ) -> tuple[BindingTable, jnp.ndarray]:
    """scan_oconst / scan_ovar_bound: subject free, object bound — expand
    subjects out of the (p, o) run in POS order (merged with the POS-side
    delta when one is overlaid)."""
    active = _active(table)
    o_vals = _term_values(table.rows, b.obj_src, ctx.const_vec)
    key = _composite_key(ctx, b, o_vals)
    lo, hi = kops.eqrange(ctx.dev.key_po_pos, key)
    if not _has_delta(ctx.dev):
        ex = expand(lo, hi, table.valid, table.cap)
        out, ex_ops = _expand_into(ctx, b, table, ex, ctx.dev.s_pos, None)
        return out, active * (2 * ctx.logn) + ex_ops
    dp = kops.delta_probe(ctx.dev.ins_key_po, ctx.dev.tomb_pos_po, key,
                          lo, hi)
    out, ex_ops = _merged_into(
        ctx, b, table, lo, hi, dp, "po",
        ((ctx.dev.s_pos, ctx.dev.ins_s_pos),), True, False)
    return out, active * (2 * ctx.logn) + ex_ops


def scan_ovar_free(ctx: EvalCtx, b: BranchPlan, table: BindingTable
                   ) -> tuple[BindingTable, jnp.ndarray]:
    """Subject and object free: expand the whole predicate run (PSO order).

    The run is delimited by the "left" ranks of ``p*R`` and ``(p+1)*R`` —
    a single 2-query ``eqrange`` probe of the PSO key column.  With a
    delta the same 2-query batch rides ``delta_probe`` for the insert
    bounds and tombstone ranks, and the expansion merges by the (s, o)
    pair (both sides are (s, o)-lex within the predicate run).
    """
    active = _active(table)
    p = ctx.const_vec[b.pred_ci].astype(jnp.int32)
    qk = jnp.stack([p * ctx.radix, (p + 1) * ctx.radix])
    bounds, _ = kops.eqrange(ctx.dev.key_ps_pso, qk)
    lo = jnp.broadcast_to(bounds[0], table.valid.shape)
    hi = jnp.broadcast_to(bounds[1], table.valid.shape)
    if not _has_delta(ctx.dev):
        ex = expand(lo, hi, table.valid, table.cap)
        out, ex_ops = _expand_into(ctx, b, table, ex, ctx.dev.s_pso,
                                   ctx.dev.o_pso)
        return out, active * (2 * ctx.logn) + ex_ops
    il, _, tl, _ = kops.delta_probe(ctx.dev.ins_key_ps,
                                    ctx.dev.tomb_pos_ps, qk, bounds,
                                    bounds)
    dp = (jnp.broadcast_to(il[0], lo.shape),
          jnp.broadcast_to(il[1], lo.shape),
          jnp.broadcast_to(tl[0], lo.shape),
          jnp.broadcast_to(tl[1], lo.shape))
    out, ex_ops = _merged_into(
        ctx, b, table, lo, hi, dp, "ps",
        ((ctx.dev.s_pso, ctx.dev.ins_s_pso),
         (ctx.dev.o_pso, ctx.dev.ins_o_pso)), True, True)
    return out, active * (2 * ctx.logn) + ex_ops


# --------------------------------------------------------------------------
# unit-level request canonicalization (host-side; the fragment-cache key)
# --------------------------------------------------------------------------

class UnitIO(NamedTuple):
    """What one unit request reads and writes, in canonical form.

    This is the brTPF/SPF request canonicalization: a seeded unit request
    is fully determined by the unit's *structure* (case sequence with
    variables renamed to read/write slots), the constants it mentions, and
    the Omega block restricted to the variables the unit actually reads —
    bindings-restricted semantics make everything else carried payload.
    Two units from different queries that canonicalize identically are the
    same server request, which is what makes star fragments cacheable
    across queries and clients (``core/fragcache.py``).
    """

    canon_sig: tuple  # branch structure with vars renamed to r/w slots
    read_cols: tuple[int, ...]  # table columns the unit reads (bound before)
    write_cols: tuple[int, ...]  # table columns the unit binds
    const_idx: tuple[int, ...]  # positions in const_vec the unit mentions


def unit_io(plan: UnitPlan) -> UnitIO:
    """Derive a unit's canonical I/O signature from its branch plan.

    Variables are renamed to ``("r", i)`` / ``("w", i)`` slots in first-use
    order; a variable bound *within* the unit (a scan'd subject, a free
    object) is a write, and later mentions of it inside the same unit refer
    to the write slot — only externally-bound variables become reads, i.e.
    the relevant bindings of the Omega block.
    """
    reads: list[int] = []
    writes: list[int] = []
    consts: list[int] = []
    written: set[int] = set()

    def slot(var: int, is_write: bool) -> tuple[str, int]:
        if is_write and var not in written:
            written.add(var)
            writes.append(var)
        if var in written:
            return ("w", writes.index(var))
        if var not in reads:
            reads.append(var)
        return ("r", reads.index(var))

    sig = []
    for b in plan.branches:
        consts.append(b.pred_ci)
        s_kind, s_idx = b.subj_src
        if s_kind == "const":
            consts.append(s_idx)
            s_tag: tuple = ("c",)
        else:  # scan cases bind the subject; probe cases read it
            s_tag = slot(s_idx, is_write=b.case.startswith("scan"))
        o_kind, o_idx = b.obj_src
        if o_kind == "const":
            consts.append(o_idx)
            o_tag: tuple = ("c",)
        else:
            o_tag = slot(o_idx, is_write=b.case.endswith("ovar_free"))
        sig.append((b.case, s_tag, o_tag))
    return UnitIO(tuple(sig), tuple(reads), tuple(writes), tuple(consts))


def unit_request_key(io: UnitIO, const_vals: tuple[int, ...],
                     omega_block: np.ndarray, cap: int,
                     epoch: int = 0) -> tuple:
    """Canonical hashable key for one seeded unit request.

    ``const_vals`` are the unit's constants in branch order;
    ``omega_block`` the valid rows restricted to ``io.read_cols`` (int32,
    C-contiguous).  ``cap`` is part of the key because overflow clamping
    and the ops account depend on the table capacity.  ``epoch`` is the
    store epoch (``TripleStore.epoch``) the request is evaluated against:
    folding it into the key guarantees responses computed before a store
    mutation can never alias requests issued after it, even through a
    pod-shared cache (``core/fragcache.py`` additionally drops stale
    entries lazily on lookup).
    """
    block = np.ascontiguousarray(omega_block, dtype=np.int32)
    return (io.canon_sig, const_vals, cap, epoch, block.shape[0],
            block.tobytes())


def unit_digest_key(io: UnitIO, const_vals: tuple[int, ...], cap: int,
                    epoch: int, n_in: int,
                    digest: tuple[int, int, int, int]) -> tuple:
    """Digest form of ``unit_request_key``: the Omega block represented by
    its on-device fingerprint instead of its raw bytes.

    ``digest`` is ``kops.fingerprint_rows`` over the valid prefix of the
    block's read columns (or ``ref.fingerprint_prefix_np`` of the same
    prefix on host-replayed state — bit-identical by construction), and
    ``n_in`` the prefix length.  The scheduler keys the fragment cache
    with this form so a unit step ships 16 bytes per lane to the host
    instead of the whole Omega block.  The ``"fp32x4"`` tag keeps the two
    key forms structurally disjoint — a digest key can never alias a
    byte key that happens to contain the same integers.  Collision risk
    across distinct blocks is that of a 128-bit hash (~2^-64 per pair),
    far below any operational concern.
    """
    return (io.canon_sig, const_vals, cap, epoch, int(n_in),
            ("fp32x4", tuple(int(x) for x in digest)))


BRANCH_EVALUATORS: dict[str, BranchEvaluator] = {
    "probe_oconst": probe_filter,
    "probe_ovar_bound": probe_filter,
    "probe_ovar_free": probe_ovar_free,
    "scan_oconst": scan_obound,
    "scan_ovar_bound": scan_obound,
    "scan_ovar_free": scan_ovar_free,
}


def log_factor(n: int) -> int:
    """``ceil(log2 n)`` floored at 1 — the cost model's binary-search
    factor.  The single point of truth: the serial evaluator, the
    scheduler's host accounting and the sharded lowering's static
    ``logn`` must all derive it identically, or the byte-identity of
    their cost accounts silently breaks."""
    return max(1, int(math.ceil(math.log2(max(int(n), 2)))))


def eval_unit(dev: StoreArrays, radix: int, plan: UnitPlan,
              const_vec: jnp.ndarray, table: BindingTable,
              owner: tuple[jnp.ndarray, int] | None = None,
              logn: int | None = None
              ) -> tuple[BindingTable, jnp.ndarray, jnp.ndarray]:
    """Evaluate one unit seeded with ``table``; returns (table, ops, peak).

    ``ops`` counts probe/expansion work (device scalar) — the server/client
    load accounting uses it.  Log-factors of binary searches are folded in.
    ``logn`` is the cost model's binary-search factor and must be derived
    from the *logical* triple count (``log_factor(store.n_triples)``) —
    under a delta overlay the physical base length differs from the
    logical store size, and the ops account must stay byte-identical to a
    from-scratch rebuilt store's.  ``None`` falls back to the base-array
    length (exact whenever the delta is empty).

    ``peak`` is the max row count at any branch boundary, input included —
    on a non-overflowing evaluation this is exactly the capacity the unit
    *needed* (an expansion's post-branch count equals its unclamped total
    when it fits), which is what the capacity planner records as the
    unit's high-water mark (``core/capacity.py``).  On an overflowed
    evaluation it is clamped at the capacity and unused.

    ``owner`` is the distributed runtime's ``(my_shard, n_shards)``: on a
    subject-hash sharded store only bound-subject (probe-first) units are
    owner-maskable — a scan-first unit expands subjects out of the local
    shard, which owns them by construction.
    """
    if logn is None:
        logn = log_factor(dev.key_ps_pso.shape[0])
    if owner is not None and not plan.branches[0].case.startswith("probe"):
        owner = None
    ctx = EvalCtx(dev, radix, const_vec, logn, owner)
    ops_total = jnp.int64(0)
    peak = table.count()
    for b in plan.branches:
        table, delta = BRANCH_EVALUATORS[b.case](ctx, b, table)
        ops_total = ops_total + delta
        peak = jnp.maximum(peak, table.count())
    return table, ops_total, peak
