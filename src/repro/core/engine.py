"""The four RDF interfaces as query engines: SPF, brTPF, TPF, endpoint.

All four share one seeded left-deep evaluator (``server.eval_unit``); they
differ in (a) unit granularity — star patterns for SPF/endpoint, single
triple patterns for TPF/brTPF — and (b) the interface cost model:

                 unit        Omega block   where joins run    NRS per unit
    TPF          triple      1             client             |Omega| (+pages)
    brTPF        triple      30            server (bind)      ceil(|Omega|/30)
    SPF          star        30            server (star+bind) ceil(|Omega|/30)
    endpoint     star        unbounded     server             1 per query

Join order across units: most selective (lowest Def. 6 cardinality
estimate) first, greedily constrained to units sharing a variable with the
already-bound set (no accidental cartesian products) — the client strategy
of Section 5.1.  NRS/NTB are computed *exactly* from result counts inside
the traced computation; wall-clock throughput modelling on top of these is
the benchmark layer's job.

Execution model
---------------
Every execution path in the system is an instantiation of **one unit
evaluator** behind the shared batch-step factory
(``distributed.make_batch_step`` via ``core/stepper.py``): the serial
ladder, vmapped scheduler waves, replicated mesh waves, subject-hash
*sharded* mesh waves, and the distributed engine's whole-query lanes all
run the same branch evaluators — the lowering (and its collective
schedule, or absence) is the only difference, and it is invisible in the
bytes.

A *single* query (``run``) executes unit-by-unit, each unit a jitted step
keyed by the unit's structure — so structurally identical units share
compiles across queries and with the scheduler.  Table capacities come
from the capacity planner (``core/capacity.py``): each unit starts at a
data-informed *snug* capacity — the high-water mark (true peak row count)
last observed for exactly this ``(plan signature, constants, unit)`` at
the current store epoch, or the degree oracle's bound *seeded from the
observed input prefix* for cold plans (``unit_start_cap``), so capacities
shrink back after a fat intermediate collapses instead of dragging the
query maximum through the tail.  Capacity overflow (the timeout analogue)
is handled *resumably*: the last valid binding table is the checkpoint,
and only the overflowed unit's table regrows at 4x — the prefix units are
never re-executed.  At ``max_cap`` the overflow flag latches and
evaluation continues on the truncated table, exactly like the blind
ladder's give-up rung.

Because a non-overflowing evaluation's valid rows and cost account are
independent of the capacity it ran at, this path is byte-identical (rows
and gross ``QueryStats``) to the pre-PR 4 blind ladder — restart the whole
query at 4x capacity until it fits — which survives behind
``EngineConfig(capacity_planner=False)`` as a single jitted whole-query
function and is pinned against the planned path by the ladder-parity
suite (``tests/test_capacity.py``).

A query *load* (``run_load``) does not loop over ``run``: it delegates to
the concurrent scheduler (``core/scheduler.py``), which buckets requests
by plan signature, pads buckets to fixed-width waves, and picks each
wave's lowering — single-host vmap, replicated mesh lanes, or the sharded
mesh step — with the star-fragment cache (``core/fragcache.py`` —
frequency-aware admission, negative-result side table, store-epoch
invalidation) consulted digest-first between unit steps and cache hits
*replayed on device* (``kops.replay_delta``), so all-hit waves never
materialise Omega blocks on the host.  The two paths return byte-identical
valid result rows and identical gross ``QueryStats``; the scheduler
additionally fills the cache fields (``cache_hits``, ``cache_misses``,
``nrs_saved``, ``ntb_saved``) that ``run`` leaves zero.  The scheduler
seam is what turns the per-query cost simulator into a load-serving
system: repeated star/bind requests across queries and simulated clients
are served from the cache instead of the store.

The *distributed* load path (``DistributedEngine.run_load``) is the same
scheduler handed a device mesh, the engine's pod-shared cache/planner and
its ``data`` axis: wide waves run against the subject-hash sharded store
(1/n_data of the index per device — the memory-scaling mode) with wave
lanes spread over the remaining axes and one order-restoring collective
per unit (``stepper.sharded_unit_step`` hoists exactly the per-unit
merge the whole-query lane evaluator uses — an ``all_gather`` + lexsort
or a log2(shards)-round pairwise k-way merge, byte-identical either
way); narrower waves fall back to replicated mesh lanes or vmap.  Waves
at the overflow-latch rung (``cap == max_cap``) stay sharded too: the
step's latch mode merges after every branch, so mid-unit truncation
happens in global serial row order — the one case that used to force a
whole-table lowering.  The sharded step rebuilds the
exact serial cost account from scalar psums of the branch-boundary counts
and sorts its gather by provenance + drawn-value columns back into serial
row order, so the choice of lowering — and the shard count — is invisible
in results, stats, overflow flags, retry sequences and cache digests,
which is exactly what the shard-parametrized scheduler tests and the
property suite pin.

The store under all of this is **live**: ``TripleStore.apply_delta``
overlays sorted insert rows and tombstones on the immutable base index,
and every dispatched probe becomes a merged eqrange over base + delta
(``kops.delta_probe`` beside the base probe — probe cost grows with the
delta, never the store), with ``n_triples`` the logical live count that
``log_factor``/``probe_op_cost`` derive from.  The scheduler serves
*through* writes, epoch-pipelined: writes queue (``submit_write`` /
``ingest``) and apply only at wave boundaries; each in-flight job pins
the epoch view (device arrays, logn, probe-op cost) its first wave
served on, so its overflow retries finish byte-identical on the old
epoch while waves formed after the boundary serve the new one.  Across
the boundary the warm state *carries*: fragments and planner high-water
marks whose constants avoid the delta's changed predicates are re-keyed
to the new epoch instead of swept (``sync_epoch`` with
``changed_preds_since`` attribution), and a threshold compaction
(``maybe_compact``) folds the delta into a fresh base bit-identical to
a from-scratch build — changing no logical triple, so everything
carries.  The invariant is the same one the lowerings pin: any delta
state, any epoch sequence, any interface returns bytes identical to a
stop-the-world rebuild of the merged triple set
(``tests/test_live_ingest.py``).

Cost accounting: the TPF page path charges fragment location at the
*dispatched* probe primitive's cost (``kops.probe_op_cost`` — bisection
steps on the jnp oracle, column-stream tile passes on Pallas), so
TPF-vs-SPF server-op comparisons track the kernel layer actually serving
the requests.

Observability: with ``repro.obs`` enabled, this execution model is
recorded live as ``obs.span`` layer boundaries — ``sched.drain`` →
``sched.plan``, ``wave`` (lowering, width, cap, wave number) → ``unit``
→ ``cache.probe`` / ``wave.lower`` / ``unit.step`` (dispatch) /
``unit.wait`` (the device's time) / ``unit.pull`` / ``cache.insert`` /
``cache.replay_device`` / ``overflow.resume`` / ``unit.account``, then
``wave.finalize``; ``engine.query`` → ``unit`` → ``unit.step`` on the
single-query path.  Each span lands in the JAX profiler's host plane
(on the device's clock) and sums its seconds into the owning registry
(``sched.span_s.<name>``); an attached ``SpanTracer`` also keeps the
per-query ``query`` async spans, ``gather.merge`` and the ``kernel.*``
instants marking trace-time backend dispatch, and
``obs.tracer.export_chrome`` writes them as a Perfetto-loadable
timeline.  Every counter in this module's components is a named
instrument in the same registry (``QueryScheduler.snapshot``).  Off by
default at zero overhead, and no span blocks on the device — the traced
and untraced executions are byte-identical.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, replace
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.bindings import BindingTable, unit_table
from repro.core.capacity import CapacityPlanner
from repro.core.patterns import BGP, StarPattern, star_decomposition
from repro.core.server import UnitPlan, eval_unit, plan_unit
from repro.kernels import ops as kops
from repro.rdf.store import StoreArrays, TripleStore


INTERFACES = ("tpf", "brtpf", "spf", "endpoint")


@dataclass(frozen=True)
class EngineConfig:
    interface: str = "spf"
    page_size: int = 50  # LDF page size (paper: 50)
    omega: int = 30  # max bindings per request (paper: 30)
    cap: int = 4096  # binding-table capacity (the timeout analogue)
    max_cap: int = 1 << 20  # overflow retry ceiling (4x growth); then give up
    # size capacities from the data (degree oracle + high-water marks,
    # core/capacity.py) and resume overflow at the failing unit; False
    # restores the blind whole-query 4x retry ladder (byte-identical)
    capacity_planner: bool = True
    # wire-format constants for NTB (bytes): pattern/bindings serialisation
    request_base_bytes: int = 300  # HTTP request overhead
    page_header_bytes: int = 200  # per-page metadata/controls (Def. 4 M', C')
    term_bytes: int = 4  # dictionary-encoded term on the wire


class QueryStats(NamedTuple):
    """Per-query cost account (device scalars or host ints, all integral).

    ``nrs``/``ntb`` are *gross* counts — what the interface protocol costs
    with no cache in front of the server.  The scheduler path fills the
    cache fields: ``nrs_saved``/``ntb_saved`` are the requests/bytes served
    by the star-fragment cache (or by collapsing onto an identical
    in-flight request) that never reached the *origin server*, so the
    effective origin load is ``nrs - nrs_saved`` / ``ntb - ntb_saved``.
    Clients still pay the wire for cache-served responses — benchlib's
    model charges full wire cost and relieves only the server term.  The
    serial ``run`` path leaves all four at zero.
    """

    nrs: jnp.ndarray  # number of requests to the server
    ntb: jnp.ndarray  # transferred bytes, both directions
    server_ops: jnp.ndarray  # server-side work units
    client_ops: jnp.ndarray  # client-side work units
    n_results: jnp.ndarray
    overflow: jnp.ndarray  # bool
    cache_hits: jnp.ndarray = 0  # unit requests served from the cache
    cache_misses: jnp.ndarray = 0  # unit requests that hit the store
    nrs_saved: jnp.ndarray = 0  # requests the cache kept off the origin
    ntb_saved: jnp.ndarray = 0  # bytes the cache kept off the origin


@dataclass(frozen=True)
class QueryPlan:
    units: tuple[UnitPlan, ...]
    n_vars: int
    consts: tuple[int, ...]
    interface: str

    @property
    def signature(self) -> tuple:
        return (self.interface, self.n_vars,
                tuple(u.signature for u in self.units))


# --------------------------------------------------------------------------
# planning
# --------------------------------------------------------------------------

def _units_for_interface(bgp: BGP, interface: str) -> list[StarPattern]:
    stars = star_decomposition(bgp)
    if interface in ("spf", "endpoint"):
        return stars
    # TPF/brTPF: one unit per triple pattern
    units: list[StarPattern] = []
    for star in stars:
        for p, o in star.branches:
            units.append(StarPattern(star.subject, ((p, o),)))
    return units


def plan_query(store: TripleStore, bgp: BGP, cfg: EngineConfig) -> QueryPlan:
    """Greedy selective-first join ordering over units (Section 5.1)."""
    units = _units_for_interface(bgp, cfg.interface)

    # Estimate each unit's cardinality with an *unseeded* plan (this is what
    # the client learns from each unit's first-page metadata).
    est = []
    for u in units:
        scratch: list[int] = []
        p, _ = plan_unit(store, u, frozenset(), scratch)
        est.append(p.est_card)

    remaining = list(range(len(units)))
    bound: frozenset[int] = frozenset()
    consts: list[int] = []
    ordered: list[UnitPlan] = []
    while remaining:
        # prefer units connected to the bound set; among those, lowest card
        connected = [i for i in remaining
                     if not bound or set(units[i].variables()) & bound]
        pool = connected if connected else remaining
        nxt = min(pool, key=lambda i: est[i])
        plan, bound = plan_unit(store, units[nxt], bound, consts)
        ordered.append(plan)
        remaining.remove(nxt)
    return QueryPlan(tuple(ordered), bgp.n_vars, tuple(consts), cfg.interface)


# --------------------------------------------------------------------------
# traced execution + cost model
# --------------------------------------------------------------------------

def _ceil_div(a: jnp.ndarray, b: int) -> jnp.ndarray:
    return (a + b - 1) // b


def _execute(plan_sig_static: tuple, plans: tuple[UnitPlan, ...], n_vars: int,
             cfg: EngineConfig, radix: int, logn: int, probe_ops: int,
             dev: StoreArrays, const_vec: jnp.ndarray
             ) -> tuple[BindingTable, QueryStats]:
    del plan_sig_static  # only used as the jit cache key
    table = unit_table(cfg.cap, max(n_vars, 1))
    nrs = jnp.int64(0)
    ntb = jnp.int64(0)
    server_ops = jnp.int64(0)
    client_ops = jnp.int64(0)
    tb = cfg.term_bytes

    for k, up in enumerate(plans):
        in_count = table.count()
        table, ops, _ = eval_unit(dev, radix, up, const_vec, table,
                                  logn=logn)
        out_count = table.count()
        matched_triples = out_count * up.n_triple_patterns

        if cfg.interface == "endpoint":
            # all work server-side; traffic accounted once at the end
            server_ops = server_ops + ops
            continue

        # ---- request counting -------------------------------------------
        # one metadata request per unit (first page probe for join ordering)
        meta_req = jnp.int64(1)
        if cfg.interface == "tpf":
            blocks = jnp.maximum(in_count, 1) if k > 0 else jnp.int64(1)
        else:  # brtpf / spf: Omega-blocked requests
            blocks = _ceil_div(jnp.maximum(in_count, 1), cfg.omega) if k > 0 \
                else jnp.int64(1)
        pages = _ceil_div(jnp.maximum(out_count, 1), cfg.page_size)
        # page fetches beyond each block's first page are extra requests
        extra_pages = jnp.maximum(pages - blocks, 0)
        nrs = nrs + meta_req + blocks + extra_pages

        # ---- byte counting ----------------------------------------------
        sent = (blocks + meta_req + extra_pages) * cfg.request_base_bytes
        if cfg.interface in ("brtpf", "spf") and k > 0:
            # bindings serialised with each block
            n_bound_vars = len(
                {v for b in up.branches for src in (b.subj_src, b.obj_src)
                 if src[0] == "var" for v in [src[1]]})
            sent = sent + in_count * max(n_bound_vars, 1) * tb
        recv = (matched_triples * 3 * tb
                + (pages + meta_req) * cfg.page_header_bytes)
        ntb = ntb + sent + recv

        # ---- work split ---------------------------------------------------
        if cfg.interface == "tpf":
            # server only locates/pages each instantiated fragment; the
            # client performs the joins (merging bindings into its table).
            # The per-probe charge is the dispatched primitive's cost model
            # (kops.probe_op_cost: bisection steps on the jnp oracle,
            # column-stream tile passes on the Pallas path), so TPF-vs-SPF
            # server-op comparisons use the same accounting as the kernel
            # layer it actually runs.  ``probe_ops`` comes from the
            # *logical* triple count (delta overlay included), so the
            # account matches a from-scratch rebuilt store byte-for-byte.
            server_ops = server_ops + blocks * probe_ops + matched_triples
            client_ops = client_ops + ops
        else:
            server_ops = server_ops + ops
            client_ops = client_ops + out_count  # client merges results

    n_results = table.count()
    if cfg.interface == "endpoint":
        nrs = jnp.int64(1)
        ntb = (jnp.int64(cfg.request_base_bytes)
               + n_results * n_vars * tb + jnp.int64(cfg.page_header_bytes))

    stats = QueryStats(
        nrs=nrs, ntb=ntb, server_ops=server_ops, client_ops=client_ops,
        n_results=n_results, overflow=table.overflow,
    )
    return table, stats


class QueryEngine:
    """Runs BGP queries against a TripleStore via one of the four interfaces.

    ``planner`` may be shared across engines and schedulers (the pod-shared
    high-water-mark memory — ``DistributedEngine.pod_planner`` does exactly
    this); by default each engine owns one.
    """

    def __init__(self, store: TripleStore, cfg: EngineConfig,
                 planner: "CapacityPlanner | None" = None):
        if cfg.interface not in INTERFACES:
            raise ValueError(f"unknown interface {cfg.interface!r}")
        self.store = store
        self.cfg = cfg
        self.planner = planner if planner is not None \
            else CapacityPlanner(store, cfg)
        self._cache: dict[tuple, callable] = {}

    def plan(self, bgp: BGP) -> QueryPlan:
        return plan_query(self.store, bgp, self.cfg)

    def run(self, bgp: BGP) -> tuple[BindingTable, QueryStats]:
        """Run one query; capacity overflow (the timeout analogue) grows
        tables 4x up to ``max_cap``.

        With the capacity planner (the default) each unit starts at a
        data-informed ladder rung and overflow re-enters at the failing
        unit with only that unit's table regrown; with
        ``capacity_planner=False`` the whole query restarts at 4x until it
        fits.  Both return identical valid rows and gross stats — the
        planner changes how fast the answer is reached, never the answer.
        """
        if not obs.enabled:
            return self._run(bgp)
        with obs.span("engine.query", interface=self.cfg.interface) as sp:
            t0 = time.perf_counter()
            table, stats = self._run(bgp)
            # the latency histogram lives in the *global* obs registry (the
            # engine has no per-instance registry); obs-gated, so the
            # disabled path never mutates it
            obs.registry.observe("engine.query_latency_s",
                                 time.perf_counter() - t0)
            sp.note(n_results=stats.n_results)
        return table, stats

    def _run(self, bgp: BGP) -> tuple[BindingTable, QueryStats]:
        plan = self.plan(bgp)
        if not self.cfg.capacity_planner:
            return self._run_blind(plan)
        self.planner.sync_epoch(self.store.epoch)
        caps = self.planner.unit_caps(plan)
        if not caps or max(caps) <= self.cfg.cap:
            # the oracle/HWM proves the base capacity cannot overflow:
            # take the single fused whole-query jit — one dispatch, no
            # per-unit host syncs (byte-identical either way; this keeps
            # selective queries at blind-path speed)
            return self._run_blind(plan)
        return self._run_planned(plan)

    def _run_blind(self, plan: QueryPlan) -> tuple[BindingTable, QueryStats]:
        """The pre-planner blind ladder: restart the whole query at 4x
        capacity until it fits (the ladder-parity baseline).  One jitted
        whole-query function per (signature, cap)."""
        from repro.core.server import log_factor

        const_vec = jnp.asarray(np.asarray(plan.consts, dtype=np.int64))
        cap = self.cfg.cap
        # logn/probe_ops derive from the *logical* triple count, which can
        # change without any device-array shape changing (a tombstone-only
        # delta keeps every shape) — the epoch in the key is what retraces
        # the baked-in cost constants when it does
        n = self.store.n_triples
        while True:
            cfg = replace(self.cfg, cap=cap)
            key = (plan.signature, cap, self.store.epoch)
            fn = self._cache.get(key)
            if fn is None:
                fn = jax.jit(
                    partial(_execute, plan.signature, plan.units, plan.n_vars,
                            cfg, self.store.radix, log_factor(n),
                            kops.probe_op_cost(n)))
                self._cache[key] = fn
            table, stats = fn(self.store.device, const_vec)
            if not bool(stats.overflow) or cap >= self.cfg.max_cap:
                return table, stats
            cap *= 4

    def _run_planned(self, plan: QueryPlan
                     ) -> tuple[BindingTable, QueryStats]:
        """Unit-stepped execution with planner capacities + resumable
        overflow (see the module docstring's execution model).  Stats are
        host ints built through ``stepper.unit_cost`` — the same twin of
        ``_execute``'s accounting the scheduler uses.

        Each unit starts at ``planner.unit_start_cap`` — its HWM, or the
        *seeded* oracle bound chained from the observed input prefix —
        so capacities shrink back to snug after a fat intermediate
        collapses (hourglass plans no longer drag the fat unit's capacity
        through their tail; byte-safe by capacity-independence)."""
        from repro.core import stepper

        cfg = self.cfg
        store = self.store
        dev = store.device
        from repro.core.server import log_factor

        const_vec = jnp.asarray(np.asarray(plan.consts, dtype=np.int64))[None]
        n_vars = max(plan.n_vars, 1)
        n = store.n_triples  # logical count: delta overlay included
        logn = log_factor(n)
        probe_ops = kops.probe_op_cost(n)

        cap = self.planner.unit_start_cap(plan, 0, 1) if plan.units \
            else cfg.cap
        seed = unit_table(cap, n_vars)
        rows, valid = seed.rows, seed.valid
        ovf_dev = seed.overflow
        overflow = False
        n_in = 1
        max_peak = 1
        nrs = ntb = server = client = 0
        for k, up in enumerate(plan.units):
            with obs.span("unit", k=k) as usp:
                # once overflow latches (at max_cap) the blind ladder's give-up
                # rung runs everything at max_cap on the truncated table — do
                # exactly that for byte-identity
                want = cfg.max_cap if overflow \
                    else self.planner.unit_start_cap(plan, k, n_in)
                if want != cap:
                    rows, valid = stepper.reseat(rows, valid, want)
                    cap = want
                while True:
                    step = stepper.serial_unit_step(up, store.radix, logn)
                    with obs.span("unit.step", k=k, cap=cap):
                        r_o, v_o, o_o, ops_o, cnt_o, peak_o = step(
                            dev, const_vec, rows[None], valid[None],
                            jnp.asarray([overflow]))
                    unit_ovf = bool(np.asarray(o_o)[0])
                    if unit_ovf and not overflow and cap < cfg.max_cap:
                        # resumable overflow: regrow only this unit's table,
                        # seeded with the checkpointed (pre-step) prefix
                        with obs.span("overflow.resume", unit=k, cap=cap):
                            cap = min(cap * 4, cfg.max_cap)
                            rows, valid = stepper.reseat(rows, valid, cap)
                        continue
                    break
                rows, valid, ovf_dev = r_o[0], v_o[0], o_o[0]
                out_count = int(np.asarray(cnt_o)[0])
                d = stepper.unit_cost(cfg, k, up, n_in,
                                      out_count, int(np.asarray(ops_o)[0]),
                                      probe_ops)
                nrs += d[0]
                ntb += d[1]
                server += d[2]
                client += d[3]
                if not unit_ovf:
                    # record what the unit NEEDED (its true peak row count),
                    # not the capacity it happened to run at — warm runs then
                    # get exactly-right-sized tables even where the chained
                    # oracle bound (a monotone product) overshoots
                    peak = int(np.asarray(peak_o)[0])
                    self.planner.observe_unit(
                        plan, k, self.planner.snug(max(peak, n_in)))
                    max_peak = max(max_peak, peak, n_in)
                overflow = unit_ovf
                n_in = out_count
                usp.note(n_out=out_count)

        n_results = n_in
        if cfg.interface == "endpoint":
            nrs, ntb = stepper.endpoint_totals(cfg, n_results, plan.n_vars)
        # whole-query HWM (the scheduler's single-cap form): the snug cap
        # covering the largest true peak, or max_cap on a latched overflow
        self.planner.observe_query(
            plan, cfg.max_cap if overflow else self.planner.snug(max_peak))
        stats = QueryStats(
            nrs=nrs, ntb=ntb, server_ops=server, client_ops=client,
            n_results=n_results, overflow=overflow,
        )
        return BindingTable(rows, valid, ovf_dev), stats

    def run_load(self, queries: list[BGP],
                 scheduler=None) -> tuple[list[BindingTable], list[QueryStats]]:
        """Serve a query list through the concurrent scheduler.

        Batches plan-homogeneous queries into vmapped waves and serves
        repeated star/bind requests from the fragment cache; results are
        byte-identical (valid rows) to looping ``run`` and the gross stats
        fields match it exactly.  Pass a ``QueryScheduler`` to share its
        fragment cache (and its metrics) across calls.
        """
        from repro.core.scheduler import QueryScheduler

        sched = scheduler or QueryScheduler(self.store, self.cfg,
                                            planner=self.planner)
        return sched.run_queries(queries)


def results_as_numpy(table: BindingTable) -> np.ndarray:
    """Valid rows as a numpy array (for tests / result checking)."""
    rows = np.asarray(table.rows)
    valid = np.asarray(table.valid)
    return rows[valid]
