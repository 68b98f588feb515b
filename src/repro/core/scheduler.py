"""Concurrent query scheduler: mixed loads as batched, cache-aware work.

The paper's headline result is throughput under concurrent load (up to 128
clients), so the serving path must *be* a load server, not a serial loop.
This module accepts an interleaved stream of queries from N simulated
clients and turns it into device-efficient work:

1. **Bucket** — requests are planned (memoised per query) and grouped by
   plan signature; identical in-flight ``(signature, constants)`` requests
   collapse onto one job whose response is fanned out (request collapsing,
   the concurrent analogue of a cache hit).  Plan homogeneity — the
   restriction ``DistributedEngine.plan_batch`` exposes to callers — is an
   internal bucketing detail here.  Starting capacities are data-informed:
   the capacity planner (``core/capacity.py``) serves the high-water mark
   last observed for the query (pod-shared, epoch-tagged) or the degree
   oracle's bound for cold plans, so warm loads never climb the 4x ladder.
2. **Pad** — each bucket is cut into waves of at most ``lanes`` jobs; a
   wave runs at the smallest power-of-two lane width that fits it and is
   padded with no-op lanes (empty seed table, zero constants), so the
   compiled step set stays small (one per width) without 16-wide padding
   of a single huge-capacity retry.
3. **Dispatch** — a wave executes unit-by-unit through the shared batch
   step factory (``distributed.make_batch_step`` via ``core/stepper.py``),
   and the factory is instantiated *per wave*, picking among **three
   lowerings** by wave width, store size and capacity:

   - **vmap** — narrow waves (and every wave of a mesh-less scheduler):
     single-host ``jit(vmap(...))``, store broadcast.
   - **replicated mesh** — waves wide enough to span the mesh's lane
     slots: ``shard_map`` with every mesh axis a lane axis and the store
     replicated per device (``data_axis=None``).
   - **sharded mesh** — a scheduler built with a ``data_axis`` naming one
     of its mesh axes shards the store by subject hash along it (1/n_data
     of the index per device — the memory-scaling mode) and spreads wave
     lanes over the remaining axes; each unit step is local branch
     evaluation plus one order-restoring collective
     (``stepper.sharded_unit_step``).  The collective is either an
     ``all_gather`` + lexsort or a log2(n_shards)-round pairwise k-way
     merge (``SchedulerConfig.shard_merge``), byte-identical.  Waves at
     the overflow-latch rung (``cap == max_cap``) stay sharded too:
     latch semantics truncate mid-unit in *global* row order, which the
     step reproduces by merging after every branch (the merged table is
     replicated, and re-partitions by store locality on the next
     branch), instead of falling back to a whole-table lowering.

   All three run the same per-lane evaluator, so the pick is pure
   placement — valid rows, gross stats, overflow flags and retry
   sequences stay byte-identical (the sharded step rebuilds the exact
   serial cost account from scalar psums and restores serial row order in
   its gather).  Unit steps are jit-cached by unit structure (and mesh),
   so buckets with different query signatures still share compilations of
   their common stars.  Wave state stays device-resident between steps:
   per unit only per-lane digests, counts and flags cross to the host.
4. **Cache** — between unit steps the scheduler fingerprints every lane's
   seeded request *on device* (``kops.fingerprint_rows`` over the valid
   prefix of the unit's read columns) and consults the pod-shared
   star-fragment cache (``core/fragcache.py``) with the digest-form key
   (``server.unit_digest_key``, tagged with the store epoch): the Omega
   block itself never round-trips to the host just to be hashed into a
   key.  Replay runs on device too (``stepper.replay_step`` /
   ``kops.replay_delta``): when every active lane hits, the cached
   fragment deltas — the small objects — are uploaded and scattered onto
   the lanes' seed prefixes in place, so an all-hit wave performs **zero**
   host Omega materialisations (``SchedMetrics.host_block_pulls`` counts
   the exceptions: a miss records that lane's output prefix as the
   replayable delta, an overflow-retire pulls its checkpoint seed).  A
   dispatched step reaches the host in one transfer: its stats and, when
   a lane missed and the wave is within ``WAVE_PULL_BYTES``, the whole
   wave's output rows, from which each miss's prefix is sliced on the
   host.
   The digest is a pure function of the valid prefix, which is
   byte-identical across all three lowerings and every shard count, so
   fragments recorded under one lowering serve waves under any other.
   Admission is frequency-aware over a constant-space count-min sketch,
   with empty fragments in a negative side table.  Exact per-query
   savings land in ``QueryStats`` (``cache_hits``/``cache_misses``/
   ``nrs_saved``/``ntb_saved``).  One cache instance may be shared by any
   number of schedulers (``DistributedEngine.pod_cache``); a store
   mutation bumps ``TripleStore.epoch`` and stale fragments are swept on
   the next drain.

Provenance: unit steps carry an extra int32 table column seeded with the
row index, so the scheduler can read each output row's source row off the
result — that is what makes computed fragments replayable as deltas
without re-deriving join provenance on the host.

Capacity overflow is *resumable*: when a lane overflows at unit k, only
that query is requeued — re-bucketed under ``(signature, 4x cap, unit k)``
with the checkpointed pre-step table as its seed and its cost account
carried over, so units 0..k-1 are never re-executed (the blind
re-run-everything ladder survives only as what a retried wave would have
recomputed anyway).  Results stay byte-identical to the serial path: a
non-overflowing unit's valid rows and cost account are independent of the
capacity (and seed capacity) it ran at.  Stats match the serial engine's
exactly on the gross fields (``stepper.unit_cost`` mirrors
``engine._execute``; drift is pinned down by tests comparing full
``QueryStats`` across both paths).
"""

from __future__ import annotations

import math
import time
from collections import OrderedDict
from dataclasses import dataclass, replace
from typing import Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import faults, obs
from repro.compat import auto_axes
from repro.core import stepper
from repro.core.bindings import BindingTable
from repro.core.capacity import CapacityPlanner
from repro.core.engine import EngineConfig, QueryPlan, QueryStats, plan_query
from repro.core.fragcache import FragmentCache, FragmentEntry
from repro.core.patterns import BGP
from repro.core.server import log_factor, unit_digest_key, unit_io
from repro.kernels import ops as kops
from repro.rdf.store import TripleStore


# The largest unit-step output, rows and provenance (``B * cap * (V + 1)``
# int32 words, in bytes), that comes to the host whole, in the same
# transfer as the step's stats, when a lane missed the cache.  Past it a
# missed lane's prefix is pulled on its own, two device slices and two
# round trips a lane (``QueryScheduler._insert``).  Measured on one TPU
# v5 lite (``benchmarks/pull_transfer.py``): one lane's own pull pair
# takes 3.0-3.4 ms at every wave size; the whole wave with its stats in
# one ``device_get`` 0.70 ms at 0.33 MB, 1.34 ms at 5.2 MB, 2.78 ms at
# 21 MB and 3.57 ms at 31 MB, then 17.5 ms at 42 MB and 31.7 ms at 84 MB.
# Up to 20 MiB (8 lanes x 65,536 rows x 10 words) the one transfer costs
# less than a single missed lane's pulls; at 42 MB it costs more than
# five lanes' pulls, at 84 MB more than all eight.
WAVE_PULL_BYTES = 20 << 20


@dataclass(frozen=True)
class SchedulerConfig:
    # max lane width of a dispatched wave; a wave runs at the smallest
    # power-of-two width that fits its jobs (so a 1-job overflow-retry wave
    # at a huge cap is not padded 16-wide), padded with no-op lanes
    lanes: int = 8
    use_cache: bool = True
    cache_entries: int = 4096
    # collapse identical in-flight (signature, constants) requests onto one
    # lane; their shared response counts as cache-served for the duplicates
    collapse_duplicates: bool = True
    # start jobs at planner-informed capacities (HWM or degree oracle when
    # the engine config enables the planner; the legacy per-scheduler
    # final-cap memo otherwise) instead of cfg.cap — re-submissions jump
    # straight to the last observed rung (results are byte-identical: the
    # serial path's returned table/stats also come from the final rung)
    cap_hints: bool = True
    # sharded lowering policy (only with a mesh + data_axis): minimum
    # store size for sharding to pay (below it the per-unit collective
    # dominates and replicated lanes win), and the per-shard gather
    # budget's skew margin (stepper.shard_trim: a shard ships at most
    # headroom * cap / n_shards rows per unit — "per-shard caps").  The
    # static headroom is only the *cold* trim: once a unit has run
    # sharded, the planner's pod-shared shard-peak high-water mark
    # replaces it with the measured occupancy (pow2-rounded, floored at
    # the capacity quantum) — an undershoot is byte-safe because trimmed
    # rows ride the normal overflow-retry path
    shard_min_triples: int = 0
    shard_headroom: int = 2
    # order-restoring merge for sharded waves ("auto" | "kway" |
    # "lexsort"): auto picks the recursive-doubling pairwise k-way merge
    # at every shard count (non-power-of-two counts run the padded
    # schedule — empty partner blocks, +2 rounds); "lexsort" forces the
    # all_gather + full-sort strategy and is the only remaining fallback,
    # counted in SchedMetrics.merge_lexsort_steps so it is never silent.
    # All strategies are byte-identical (stepper.select_gather_merge)
    shard_merge: str = "auto"


class Request(NamedTuple):
    rid: int
    client: int
    query: BGP
    # absolute time.perf_counter() deadline, or None (no deadline); checked
    # cooperatively at unit-step boundaries — an expired request resolves
    # as (None, stats-so-far) instead of burning the rest of its wave
    deadline: float | None = None


class _EpochView(NamedTuple):
    """One store epoch's device-resident serving snapshot.

    A wave pins the view it starts on (``_Job.view``), and overflow
    retries carry the pin — so a write applied between waves
    (``submit_write`` → ``_apply_writes``) never mixes epochs inside one
    query execution: in-flight jobs finish on the old epoch's arrays
    (the Python reference keeps them alive on device), fresh waves pick
    up the new epoch.  ``logn``/``probe_ops`` ride along because the
    cost account derives from the *logical* triple count, which moves
    with every delta epoch.
    """

    epoch: int
    dev: object  # StoreArrays — the replicated/vmap device view
    stacked: object | None  # sharded StoreArrays, when captured sharded
    logn: int
    probe_ops: int


@dataclass
class _Job:
    """One distinct query execution: a lane's worth of work at one cap.

    A resumable overflow retry re-enters at ``resume_k`` with ``seed`` (the
    checkpointed valid-prefix rows of the overflowed unit's input) and the
    cost account ``acc`` accumulated over units 0..resume_k-1.
    """

    plan: QueryPlan
    consts: tuple[int, ...]
    cap: int
    rids: list[int]
    resume_k: int = 0
    seed: np.ndarray | None = None
    acc: "_LaneAcc | None" = None
    # largest true per-unit peak row count seen so far (carried across
    # resume retries) — what observe_query records as the query's need
    peak_seen: int = 1
    # the epoch view this job's first wave served on (pinned for retries;
    # None until the job has run — fresh jobs adopt the current epoch)
    view: _EpochView | None = None


class SchedMetrics(obs.RegistryView):
    """Scheduler tallies as ``sched.*`` instruments in one
    ``MetricsRegistry`` (``obs.registry``): the attribute API is the old
    dataclass's — every ``metrics.x += 1`` site below is unchanged, and
    the fields stay the public read surface — but the registry is the
    source of truth, so ``QueryScheduler.snapshot()`` diffs
    (``snap_b - snap_a``) replace hand-subtracted before/after reads in
    ``benchlib`` and the BENCH figures.  A scheduler's cache and planner
    mount their ``cache.*`` / ``planner.*`` instruments on the same
    registry, so one snapshot covers the whole serving stack.
    """

    _PREFIX = "sched"
    _FIELDS = (
        "requests",
        "jobs",  # distinct executions after collapsing
        "waves",
        "steps",  # device unit-steps dispatched
        "mesh_steps",  # the subset routed through mesh shard_map steps
        "shard_steps",  # ...and the subset of THOSE on the sharded store
        "steps_skipped",  # unit-steps fully served by the cache
        "lane_steps",  # lanes x dispatched steps (incl. padding)
        "active_lane_steps",  # non-padding lanes among those
        "retries",  # jobs requeued (resumably) at 4x cap
        # requests expired at a unit-step boundary (cooperative deadline
        # check): answered (None, stats-so-far) — the endpoint maps the
        # None table to a "timeout" response
        "deadline_expired",
        # Omega blocks materialised on the host during unit stepping, one
        # per lane: each recorded miss's prefix (sliced from the step's
        # one transfer, or pulled on its own past the byte budget) and
        # each overflow-retire checkpoint; finalize excluded.  The
        # device-replay invariant the tests pin: an all-hit wave adds zero.
        "host_block_pulls",
        # every blocking device->host call of a wave, however many arrays
        # it carries (per unit the digest and the step's one transfer,
        # two per recorded miss past the byte budget, one per overflow
        # checkpoint, and finalize's two).  A host round trip each,
        # whatever the payload: what bounds the host's share of a unit
        # step
        "host_syncs",
        # dispatched steps whose output rows came to the host whole, in
        # the step's one transfer, for the cache to record its misses
        # (the share of steps with a miss is the engagement rate)
        "wave_pulls",
        # bytes moved by the sharded lowering's per-unit gather
        # collectives (benchlib folds these into the modeled throughput
        # so sharded BENCH numbers are not silently optimistic)
        "gather_bytes",
        # sharded steps that ran the all_gather+lexsort merge strategy —
        # the k-way fallback that remains after padded non-pow2 support
        # (explicit shard_merge="lexsort" only), counted so it is never
        # a silent performance cliff
        "merge_lexsort_steps",
    )

    @property
    def occupancy(self) -> float:
        """Mean active (non-padding) lanes per dispatched device step —
        the measured batch width benchlib's throughput model consumes."""
        return self.active_lane_steps / self.steps if self.steps else 0.0

    @property
    def pad_fraction(self) -> float:
        if not self.lane_steps:
            return 0.0
        return 1.0 - self.active_lane_steps / self.lane_steps


def interleave_clients(queries: list[BGP], n_clients: int
                       ) -> list[tuple[int, BGP]]:
    """The paper's load setup as an arrival stream: every client executes
    the load in order; arrivals interleave round-robin across clients."""
    return [(c, q) for q in queries for c in range(n_clients)]


@dataclass
class _LaneAcc:
    """Per-lane stats accumulator, carried across resume retries."""

    nrs: int = 0
    ntb: int = 0
    server: int = 0
    client: int = 0
    hits: int = 0
    misses: int = 0
    nrs_saved: int = 0
    ntb_saved: int = 0


# --------------------------------------------------------------------------
# the scheduler
# --------------------------------------------------------------------------

class QueryScheduler:
    """Serve a mixed query stream through signature buckets + fragment cache.

    ``run_queries`` is the drop-in for ``QueryEngine.run_load``; ``submit``
    + ``drain`` expose the request-stream form for simulated-client loads.
    One scheduler owns one store + engine config; the fragment cache and
    the capacity planner can be shared across schedulers by passing them
    in (the pod-shared instances — ``DistributedEngine.pod_cache`` /
    ``pod_planner`` do exactly this).

    ``mesh`` opts waves into distributed dispatch: every mesh axis becomes
    lane slots (store replicated per device), and ``_run_wave`` picks the
    mesh ``shard_map`` step whenever the wave's power-of-two width covers
    the slot count, falling back to the single-host vmap step for narrow
    waves.  A 1-device mesh is valid and routes everything through the
    shard_map lowering (how the tier-1 suite exercises the path on one
    CPU device).

    ``data_axis`` (naming one of the mesh's axes) additionally opts waves
    into the **sharded** lowering: the store is subject-hash sharded along
    it (``TripleStore.stacked_shard_arrays`` — 1/n_shards of the index per
    device) and wave lanes span the remaining axes.  ``_run_wave`` picks
    it for waves wide enough to cover those lane slots whenever the store
    clears ``scfg.shard_min_triples`` — including waves at the
    overflow-latch rung, which run the step's latch mode (per-branch
    global-order merge-and-truncate); results stay byte-identical (the
    sharded step's per-unit merge restores serial row order and its
    psums rebuild the exact serial cost account).  A ``data_axis`` of
    extent 1 is valid and exercises the sharded lowering on one device.
    """

    def __init__(self, store: TripleStore, cfg: EngineConfig,
                 scfg: SchedulerConfig | None = None,
                 cache: FragmentCache | None = None,
                 mesh: Mesh | None = None,
                 planner: CapacityPlanner | None = None,
                 data_axis: str | None = None,
                 registry: obs.MetricsRegistry | None = None):
        self.store = store
        self.cfg = cfg
        self.scfg = scfg or SchedulerConfig()
        # one registry per scheduler: SchedMetrics plus the cache./
        # planner. instruments of components this scheduler constructs
        # itself all mount here, so snapshot() covers the serving stack.
        # Pod-shared caches/planners passed in keep their own registries
        # (their stats aggregate across schedulers by design).
        self.registry = registry if registry is not None \
            else obs.MetricsRegistry()
        self.cache = cache if cache is not None else \
            FragmentCache(capacity=self.scfg.cache_entries,
                          registry=self.registry)
        self.planner = planner if planner is not None \
            else CapacityPlanner(store, cfg, registry=self.registry)
        self.mesh = mesh = auto_axes(mesh)
        if mesh is not None and data_axis is not None \
                and data_axis not in mesh.axis_names:
            data_axis = None  # a lane-only mesh: replicated/vmap picks only
        self.data_axis = data_axis
        if mesh is not None:
            # replicated lowering: every axis (data included) is lane slots
            self._lane_axes = tuple(mesh.axis_names)
            self._mesh_slots = math.prod(mesh.shape[a]
                                         for a in self._lane_axes)
            if data_axis is not None:
                self._n_shards = mesh.shape[data_axis]
                self._shard_lane_axes = tuple(a for a in mesh.axis_names
                                              if a != data_axis)
                self._shard_slots = math.prod(
                    [mesh.shape[a] for a in self._shard_lane_axes] or [1])
            else:
                self._n_shards = 0
                self._shard_lane_axes = ()
                self._shard_slots = 0
            if self.scfg.lanes < self._mesh_slots:
                # the wave-width cap must reach the slot count or wide
                # waves could never span the mesh (mesh routing would be
                # silently dead on pods wider than the default cap)
                self.scfg = replace(self.scfg, lanes=self._mesh_slots)
        else:
            self._lane_axes = ()
            self._mesh_slots = 0
            self._n_shards = 0
            self._shard_lane_axes = ()
            self._shard_slots = 0
        self.metrics = SchedMetrics(self.registry)
        self._t_submit: dict[int, float] = {}  # obs-only request walls
        self._deadlines: dict[int, float] = {}  # rid -> absolute deadline
        self._plan_memo: dict[BGP, QueryPlan] = {}
        self._cap_hints: dict[tuple, int] = {}  # legacy memo (planner off)
        self._pending: list[Request] = []
        self._next_rid = 0
        self._stacked_cache = None  # sharded store arrays, epoch-versioned
        self._stacked_epoch = store.epoch
        n = store.n_triples
        self._logn = log_factor(n)
        # TPF page-accounting charges the dispatched probe primitive's
        # cost, not an analytic logn (these refresh per epoch — the
        # logical triple count moves with every delta batch)
        self._probe_ops = kops.probe_op_cost(n)
        self._cost_epoch = store.epoch
        self._writes: list[tuple] = []  # queued (insert, delete) batches
        self._draining = False

    # ------------------------------------------------------------- requests
    def submit(self, query: BGP, client: int = 0,
               deadline: float | None = None) -> int:
        """Enqueue ``query``; ``deadline`` is an absolute
        ``time.perf_counter()`` instant after which the request may be
        expired at the next unit-step boundary (``None`` = never)."""
        rid = self._next_rid
        self._next_rid += 1
        self._pending.append(Request(rid, client, query, deadline))
        if deadline is not None:
            self._deadlines[rid] = deadline
        self.metrics.requests += 1
        if obs.enabled:
            self._t_submit[rid] = time.perf_counter()
        return rid

    def snapshot(self) -> obs.Snapshot:
        """Plain-dict snapshot of this scheduler's registry (sched.* +
        cache.* / planner.* of self-constructed components); diff two
        snapshots (``after - before``) for interval metrics instead of
        hand-subtracting field values."""
        return self.registry.snapshot()

    def run_queries(self, queries: Iterable[BGP], client: int = 0
                    ) -> tuple[list[BindingTable], list[QueryStats]]:
        """Serve ``queries`` and return (tables, stats) in input order."""
        rids = [self.submit(q, client) for q in queries]
        results = self.drain()
        tables = [results[r][0] for r in rids]
        stats = [results[r][1] for r in rids]
        return tables, stats

    def serve(self, stream: Iterable[tuple[int, BGP]]
              ) -> list[tuple[BindingTable, QueryStats]]:
        """Serve an interleaved (client, query) arrival stream in order."""
        rids = [self.submit(q, client=c) for c, q in stream]
        results = self.drain()
        return [results[r] for r in rids]

    # ---------------------------------------------------------------- ingest
    def submit_write(self, insert=None, delete=None) -> None:
        """Queue a write batch (``TripleStore.apply_delta`` arguments).

        Queued writes apply at the next **wave boundary**: the entry of
        the next ``drain``, or between waves of a drain in progress.
        In-flight jobs keep serving the epoch view they started on
        (``_EpochView`` pinning), fresh waves pick up the post-write
        epoch — writes never stall serving and serving never tears a
        write across one query's waves.
        """
        self._writes.append((insert, delete))

    def ingest(self, insert=None, delete=None) -> int:
        """Apply a write batch now (outside a drain) or queue it for the
        next wave boundary (inside one); returns the store epoch visible
        to the caller after the call."""
        self.submit_write(insert=insert, delete=delete)
        if not self._draining:
            self._apply_writes()
        return self.store.epoch

    def _apply_writes(self) -> bool:
        """Drain the write queue into the store's delta overlay; refresh
        the epoch-derived statics when the epoch moved.  Returns whether
        anything was applied."""
        if not self._writes:
            return False
        writes, self._writes = self._writes, []
        for ins, dele in writes:
            self.store.apply_delta(insert=ins, delete=dele)
        self._refresh_epoch()
        return True

    def _refresh_epoch(self) -> None:
        """Re-derive everything keyed off the store epoch: the cost-model
        statics (the *logical* triple count moved), the plan memo (plan
        ordering follows merged cardinalities, so post-write queries must
        re-plan to stay byte-identical with a rebuilt store), and the
        cache/planner sweeps (with changed-predicate carry-over)."""
        if self.store.epoch == self._cost_epoch:
            return
        n = self.store.n_triples
        self._logn = log_factor(n)
        self._probe_ops = kops.probe_op_cost(n)
        self._cost_epoch = self.store.epoch
        self._plan_memo.clear()
        self._sync_components()

    def _sync_components(self) -> None:
        """Sweep the (possibly pod-shared) cache and planner up to the
        current epoch, handing each the predicate set changed since *its*
        last sweep so untouched entries carry over instead of dropping
        (``None`` — unknown history — degrades to the full sweep)."""
        ep = self.store.epoch
        for comp in (self.cache, self.planner):
            if comp.synced_epoch != ep:
                changed = self.store.changed_preds_since(comp.synced_epoch)
                comp.sync_epoch(ep, changed_preds=changed)

    def _plan(self, query: BGP) -> QueryPlan:
        plan = self._plan_memo.get(query)
        if plan is None:
            plan = plan_query(self.store, query, self.cfg)
            self._plan_memo[query] = plan
        return plan

    def _start_cap(self, plan: QueryPlan, jkey: tuple) -> int:
        if not self.scfg.cap_hints:
            return self.cfg.cap
        if self.cfg.capacity_planner:
            return self.planner.query_cap(plan)
        return self._cap_hints.get(jkey, self.cfg.cap)

    @property
    def _stacked(self):
        """Subject-hash sharded store arrays for the sharded lowering,
        built lazily and versioned by the store epoch (mirrors
        ``DistributedEngine._stacked``: a ``bump_epoch`` forces a
        re-shard, so sharded waves can never serve pre-mutation arrays)."""
        if self._stacked_cache is None \
                or self._stacked_epoch != self.store.epoch:
            self._stacked_cache = self.store.stacked_shard_arrays(
                self._n_shards)
            self._stacked_epoch = self.store.epoch
        return self._stacked_cache

    # ---------------------------------------------------------------- drain
    def drain(self) -> dict[int, tuple[BindingTable | None, QueryStats]]:
        """Execute all pending requests; returns {rid: (table, stats)}.

        A request expired at a unit-step boundary (its absolute deadline
        passed) maps to ``(None, stats)`` — the stats accumulated up to
        the boundary — and counts in ``metrics.deadline_expired``; the
        rest of its wave is unaffected.

        Failure contract: pending requests are popped at entry, so an
        exception mid-drain *loses* them — callers owning retries (the
        endpoint's wave fault domain) re-``submit`` and call again.
        """
        requests, self._pending = self._pending, []
        if faults.plan is not None:
            faults.hit("drain", requests=len(requests))
        results: dict[int, tuple[BindingTable | None, QueryStats]] = {}

        # wave boundary zero: queued writes land before any wave starts
        self._draining = True
        self._apply_writes()

        tr = obs.tracer
        if tr:
            for req in requests:
                # per-query lifetime as an async span (queries overlap
                # waves freely); closed at finalize in _run_wave
                tr.begin_async("query", req.rid, client=req.client)

        try:
            with obs.span("sched.drain", self.metrics,
                          requests=len(requests)):
                with obs.span("sched.plan", self.metrics):
                    buckets = self._bucket(requests)
                while buckets:
                    (sig, cap, k0, _vep), jobs = buckets.popitem(last=False)
                    lanes = self.scfg.lanes
                    for i in range(0, len(jobs), lanes):
                        wave = jobs[i:i + lanes]
                        retries = self._run_wave(wave, results)
                        for job in retries:
                            # the pinned view epoch keys the bucket so
                            # retries from different epochs never share
                            # one wave
                            vep = job.view.epoch if job.view is not None \
                                else None
                            buckets.setdefault(
                                (sig, job.cap, job.resume_k, vep),
                                []).append(job)
                        # wave boundary: writes queued while serving land
                        # here — the wave that just finished served its
                        # pinned view, the next wave (and its retries, via
                        # the pins) stays torn-free
                        self._apply_writes()
        finally:
            self._draining = False
        self._t_submit.clear()  # unconditional: no leak across obs toggles
        self._deadlines.clear()
        return results

    def _bucket(self, requests: list[Request]
                ) -> OrderedDict[tuple, list[_Job]]:
        """Plan the drain's requests and bucket them by (signature, cap,
        resume unit, pinned epoch), collapsing identical in-flight
        queries onto one job."""
        # store mutated since the cache/planner last swept: reconcile
        # fragments and high-water marks now (keys are epoch-tagged, so
        # they could never alias — this reclaims touched entries' memory
        # eagerly and carries untouched-predicate entries into the new
        # epoch; the sweep state lives on the pod-shared objects so fresh
        # schedulers still trigger it)
        self._sync_components()
        buckets: OrderedDict[tuple, list[_Job]] = OrderedDict()
        job_of: dict[tuple, _Job] = {}
        for req in requests:
            plan = self._plan(req.query)
            jkey = (plan.signature, plan.consts)
            job = job_of.get(jkey) if self.scfg.collapse_duplicates else None
            if job is None:
                cap = self._start_cap(plan, jkey)
                job = _Job(plan, plan.consts, cap, [req.rid])
                job_of[jkey] = job
                buckets.setdefault((plan.signature, job.cap, 0, None),
                                   []).append(job)
                self.metrics.jobs += 1
            else:
                job.rids.append(req.rid)
        return buckets

    # ------------------------------------------------------------ deadlines
    def _job_deadline(self, job: _Job) -> float | None:
        """A collapsed job's effective deadline: the latest of its rids'
        deadlines, or ``None`` (never expire) if any rid has none — a
        no-deadline requester is owed a full result, so a duplicate with
        a deadline can never expire the shared execution under it."""
        dl = None
        for rid in job.rids:
            d = self._deadlines.get(rid)
            if d is None:
                return None
            dl = d if dl is None else max(dl, d)
        return dl

    def _expire(self, job: _Job, a: "_LaneAcc", ovf_flag: bool,
                results: dict) -> None:
        """Deliver a deadline expiry: ``(None, stats-so-far)`` per rid."""
        self.metrics.deadline_expired += len(job.rids)
        tr = obs.tracer
        stats = QueryStats(
            nrs=a.nrs, ntb=a.ntb, server_ops=a.server, client_ops=a.client,
            n_results=0, overflow=ovf_flag,
            cache_hits=a.hits, cache_misses=a.misses,
            nrs_saved=a.nrs_saved, ntb_saved=a.ntb_saved,
        )
        t1 = time.perf_counter() if obs.enabled else 0.0
        for rid in job.rids:
            results[rid] = (None, stats)
            self._deadlines.pop(rid, None)
            t0 = self._t_submit.pop(rid, None)
            if obs.enabled:
                if t0 is not None:
                    self.registry.observe("sched.query_latency_s", t1 - t0)
                if tr:
                    tr.end_async("query", rid, expired=True,
                                 wave=self.metrics.waves)

    def _wave_shard_trim(self, jobs: list[_Job], active: list[int],
                         k: int, cap: int) -> int:
        """Per-shard merge budget for this wave's unit ``k``.

        When the planner has observed the unit at this shard count (pod
        -shared shard-peak HWM, epoch-tagged), the trim is the measured
        occupancy — the max over the wave's jobs, rounded up to a power
        of two and floored at the capacity quantum so trims (static step
        args) stay logarithmically few.  If *any* active job lacks an
        observation the wave falls back to the static skew-headroom
        budget (``stepper.shard_trim``) — the cold default, and the
        parity baseline the tests pin.  An undershoot is byte-safe:
        trimmed rows set the lost flag, which rides the normal
        overflow-retry path.
        """
        best = 0
        for j in active:
            hint = self.planner.shard_peak_hint(jobs[j].plan, k,
                                                self._n_shards)
            if hint is None:
                return stepper.shard_trim(cap, self._n_shards,
                                          self.scfg.shard_headroom)
            best = max(best, hint)
        t = 1 << max(int(best) - 1, 0).bit_length()
        return min(cap, max(t, CapacityPlanner.MIN_QUANTUM))

    # ----------------------------------------------------------------- wave
    def _run_wave(self, jobs: list[_Job],
                  results: dict[int, tuple[BindingTable, QueryStats]]
                  ) -> list[_Job]:
        """Run one padded wave of same-signature, same-cap, same-resume-unit
        jobs through the per-unit stepped batch path.  Completed jobs land
        in ``results``; overflowed ones come back as resumable 4x-cap retry
        jobs seeded at the failing unit.

        The lowering is picked per wave (sharded > replicated mesh >
        vmap): with a ``data_axis``, waves wide enough to cover the
        non-data lane slots run against the subject-hash sharded store
        (unless the store is below the sharding threshold); waves at the
        overflow-latch rung stay sharded in the step's latch mode.
        Waves covering the full mesh run replicated; everything else
        takes the single-host vmap step.  One
        bucket can mix all three (e.g. a wide sharded first pass and a
        1-job vmap overflow retry) — results are byte-identical across
        them.

        Wave state lives on the device between steps: the cache phase
        ships 16-byte digests per lane, cache hits replay on device
        (uploaded deltas), and Omega blocks cross to the host only for a
        miss's recorded prefix or an overflow-retire's checkpoint
        (counted in ``metrics.host_block_pulls``) — and once at finalize
        to deliver the responses.  A dispatched step's outputs come to
        the host in one ``jax.device_get``: its stats and, when a lane
        missed and ``B * cap * (V + 1)`` int32 fit ``WAVE_PULL_BYTES``,
        the whole wave's rows and provenance (``metrics.wave_pulls``);
        past the budget each missed lane pulls its own prefix.  Every
        blocking call, the small ones included, counts in
        ``metrics.host_syncs``; each host phase is an ``obs.span``
        (``cache.probe``, ``unit.step`` for the dispatch, ``unit.wait``
        for the device time, ``unit.pull`` for the transfer,
        ``cache.insert``, ``unit.account``, ``wave.finalize``).
        """
        scfg = self.scfg
        plan, cap = jobs[0].plan, jobs[0].cap
        k0 = jobs[0].resume_k
        n_active = len(jobs)
        B = 1  # smallest power-of-two width that fits, capped at scfg.lanes
        while B < min(n_active, scfg.lanes):
            B *= 2
        # --- epoch view: pinned by retries, current for fresh jobs --------
        # (jobs in one wave share the view by bucket construction: fresh
        # buckets are all-None, retry buckets are keyed by the view epoch)
        view = next((j.view for j in jobs if j.view is not None), None)
        pinned_stale = view is not None and view.epoch != self.store.epoch
        # --- lowering pick: sharded > replicated mesh > vmap --------------
        use_shard = (self._n_shards > 0 and B >= self._shard_slots
                     and self.store.n_triples >= scfg.shard_min_triples
                     # a stale pin without a sharded snapshot serves its
                     # retry through the replicated/vmap step instead
                     and not (pinned_stale and view.stacked is None))
        # overflow-latch rung: the sharded step merges after every branch
        # (global-order truncation) instead of once per unit
        latch = use_shard and cap >= self.cfg.max_cap
        use_mesh = (not use_shard and self.mesh is not None
                    and B >= self._mesh_slots)
        lowering = "shard" if use_shard else "mesh" if use_mesh else "vmap"
        # the lane layout the wave's table takes on the mesh, for the
        # digest and replay kernels that run on it between unit steps
        lane_layout = (self.mesh, self._shard_lane_axes) if use_shard \
            else (self.mesh, self._lane_axes) if use_mesh else (None, ())
        slots = self._shard_slots if use_shard \
            else self._mesh_slots if use_mesh else 0
        if slots and B % slots:
            # non-power-of-two slot counts (e.g. a 6-device pod) would
            # otherwise never divide a power-of-two width and mesh routing
            # would silently die: round the wave up to the next slot
            # multiple instead (the extra lanes are no-op padding)
            B = -(-B // slots) * slots
        V = max(plan.n_vars, 1)
        if view is None:
            view = _EpochView(self.store.epoch, self.store.device,
                              self._stacked if use_shard else None,
                              self._logn, self._probe_ops)
        elif use_shard and view.stacked is None:
            # same-epoch pin captured on an unsharded wave: the current
            # sharded arrays ARE that epoch's snapshot
            view = view._replace(stacked=self._stacked)
        for job in jobs:
            job.view = view
        epoch = view.epoch
        dev = view.stacked if use_shard else view.dev

        consts = np.zeros((B, max(len(plan.consts), 1)), np.int64)
        for j, job in enumerate(jobs):
            consts[j, :len(job.consts)] = job.consts
        consts_dev = jnp.asarray(consts[:, :len(plan.consts)]) \
            if plan.consts else jnp.zeros((B, 0), jnp.int64)

        rows_h = np.full((B, cap, V), -1, np.int32)
        valid_h = np.zeros((B, cap), bool)
        counts = [0] * B
        for j, job in enumerate(jobs):
            if job.seed is None:
                valid_h[j, 0] = True  # fresh job: the all-unbound seed row
                counts[j] = 1
            else:  # resume: the checkpointed valid prefix
                m = job.seed.shape[0]
                rows_h[j, :m] = job.seed
                valid_h[j, :m] = True
                counts[j] = m
        ovf = np.zeros((B,), bool)
        acc = [job.acc if job.acc is not None else _LaneAcc()
               for job in jobs]
        self.metrics.waves += 1
        wave_no = self.metrics.waves

        # wave state is device-resident for the whole wave; host numpy
        # exists only in the seeds above and the finalize pull below
        rows_d = jnp.asarray(rows_h)
        valid_d = jnp.asarray(valid_h)

        retired: set[int] = set()
        retries: list[_Job] = []

        def _retire(j: int, k: int) -> None:
            job = jobs[j]
            with obs.span("overflow.resume", self.metrics, unit=k, cap=cap,
                          rid=job.rids[0]):
                # the checkpointed seed: this lane's pre-step valid prefix
                # (rows_d still holds the unit's input state at both call
                # sites) — one counted Omega-block pull
                seed = np.asarray(rows_d[j, :n_in[j]])
                self.metrics.host_block_pulls += 1
                self.metrics.host_syncs += 1
                retries.append(_Job(job.plan, job.consts,
                                    min(cap * 4, self.cfg.max_cap), job.rids,
                                    resume_k=k, seed=seed, acc=acc[j],
                                    peak_seen=job.peak_seen))
                retired.add(j)
                self.metrics.retries += 1

        with obs.span("wave", self.metrics, wave=wave_no, lowering=lowering,
                      latch=bool(latch), cap=cap, width=B, jobs=n_active,
                      resume_k=k0, units=len(plan.units) - k0) as wsp:
            for k in range(k0, len(plan.units)):
                up = plan.units[k]
                io = unit_io(up)
                active = [j for j in range(n_active) if j not in retired]
                # cooperative deadline check: a job whose every rid has an
                # expired absolute deadline is answered (None,
                # stats-so-far) here, at the unit boundary, instead of
                # burning the rest of the wave (the remaining lanes step
                # on without it)
                if active and self._deadlines:
                    now = time.perf_counter()
                    for j in list(active):
                        dl = self._job_deadline(jobs[j])
                        if dl is not None and now >= dl:
                            self._expire(jobs[j], acc[j], bool(ovf[j]),
                                         results)
                            retired.add(j)
                            active.remove(j)
                if not active:
                    break
                n_in = {j: counts[j] for j in active}

                with obs.span("unit", self.metrics, k=k,
                              lanes=len(active)) as usp:
                    if scfg.use_cache:
                        status, keys = self._probe_cache(
                            io, active, consts, cap, epoch, n_in, rows_d,
                            valid_d, lane_layout)
                    else:
                        status = {j: ("miss", None) for j in active}
                        keys = {}
                    need_step = any(s == "miss" for s, _ in status.values())
                    ops_lane: dict[int, int] = {}
                    if need_step:
                        with obs.span("wave.lower", self.metrics,
                                      lowering=lowering):
                            step = self._lower(up, view, jobs, active, k,
                                               cap, B, V, lowering, latch)
                        if faults.plan is not None:
                            faults.hit("unit.step", sig=plan.signature, k=k)
                        with obs.span("unit.step", self.metrics, k=k):
                            out = step(dev, consts_dev, rows_d, valid_d,
                                       jnp.asarray(ovf))
                        # the host waits here for the step's device time;
                        # the pulls below would block on it anyway
                        with obs.span("unit.wait", self.metrics, k=k):
                            jax.block_until_ready(out)
                        # the sharded step returns an 8th output (the pmax
                        # of per-shard row counts) that feeds the occupancy
                        # trims; the vmap/replicated steps return the
                        # common 7
                        r_o, v_o, o_o, src_o, ops_o, cnt_o, peak_o = out[:7]
                        # the missed lanes the cache records; none on a
                        # stale-pinned retry wave, whose fragments describe
                        # a superseded epoch and would only park dead
                        # weight under an old-epoch key
                        record = {j for j in active
                                  if status[j][0] == "miss"
                                  and not bool(ovf[j])} \
                            if scfg.use_cache \
                            and epoch == self.store.epoch else set()
                        # one transfer a step: the stats, and the whole
                        # wave's rows when a lane is recorded and they fit
                        # the byte budget; the misses are sliced from them
                        # on the host
                        whole = bool(record) and \
                            B * cap * (V + 1) * 4 <= WAVE_PULL_BYTES
                        occupancy = use_shard and not latch
                        pull = (ops_o, cnt_o, o_o, peak_o) \
                            + ((out[7],) if occupancy else ()) \
                            + ((r_o, src_o) if whole else ())
                        with obs.span("unit.pull", self.metrics):
                            host = jax.device_get(pull)
                            self.metrics.host_syncs += 1
                        ops_np, cnt_np, ovf_np, peak_np = host[:4]
                        if whole:
                            self.metrics.wave_pulls += 1
                            lane_rows = host[-2:]
                        else:
                            lane_rows = (r_o, src_o)
                        self.metrics.steps += 1
                        self.metrics.lane_steps += B
                        self.metrics.active_lane_steps += len(active)
                        for j in active:
                            ops_lane[j] = int(ops_np[j])
                            if bool(ovf_np[j]) and not bool(ovf[j]) \
                                    and cap < self.cfg.max_cap:
                                # resumable overflow: checkpoint this unit's
                                # input prefix (still the pre-step device
                                # state) and requeue at 4x — units 0..k-1
                                # are never re-run
                                _retire(j, k)
                                continue
                            if j in record:
                                self._insert(keys[j], io, j, *lane_rows,
                                             int(cnt_np[j]),
                                             bool(ovf_np[j]),
                                             int(ops_np[j]),
                                             int(peak_np[j]), epoch)
                        rows_d, valid_d = r_o, v_o
                        ovf = np.array(ovf_np)
                        for j in active:
                            if j not in retired:
                                counts[j] = int(cnt_np[j])
                                jobs[j].peak_seen = max(jobs[j].peak_seen,
                                                        int(peak_np[j]),
                                                        n_in[j])
                        if occupancy:
                            # feed the measured per-shard occupancy back
                            # into the planner so the next wave of this
                            # unit trims its merge to what shards actually
                            # produced.  Latch waves are skipped: their
                            # pmax runs post-merge (the replicated global
                            # count, not a per-shard block), and retired
                            # lanes are skipped because a clamped table's
                            # peak understates the true need
                            for j in active:
                                if j not in retired:
                                    self.planner.observe_shard_peak(
                                        jobs[j].plan, k, self._n_shards,
                                        int(host[4][j]))
                    else:
                        # every active lane hit: replay the cached deltas
                        # on the device (stepper.replay_step /
                        # kops.replay_delta).  The uploaded delta is the
                        # small object — the lanes' Omega blocks never
                        # cross to the host, so an all-hit wave adds zero
                        # host_block_pulls (the invariant the tests pin).
                        self.metrics.steps_skipped += 1
                        live: dict[int, FragmentEntry] = {}
                        for j in active:
                            entry = status[j][1]
                            if isinstance(entry, int):  # alias of a hit
                                entry = status[entry][1]
                            assert isinstance(entry, FragmentEntry)
                            if entry.overflow and not bool(ovf[j]) \
                                    and cap < self.cfg.max_cap:
                                # the cached unit overflowed at this cap:
                                # resume from the checkpointed seed like a
                                # computed one
                                _retire(j, k)
                                continue
                            live[j] = entry
                        if not live:  # every hit lane retired on overflow
                            usp.note(path="replay", live=0)
                            continue
                        if faults.plan is not None:
                            faults.hit("cache.replay", sig=plan.signature,
                                       k=k)
                        with obs.span("cache.replay_device", self.metrics,
                                      lanes=len(live)):
                            rows_d, valid_d = stepper.replay_step(
                                io.write_cols, *lane_layout)(
                                rows_d, *_replay_deltas(live, io, B, cap))
                        for j, e in live.items():
                            ovf[j] = bool(ovf[j]) | e.overflow
                            counts[j] = e.n_out
                            ops_lane[j] = e.ops
                            jobs[j].peak_seen = max(jobs[j].peak_seen,
                                                    e.peak, n_in[j])

                    # --- host stats accounting (twin of engine._execute) --
                    with obs.span("unit.account", self.metrics):
                        for j in active:
                            if j not in retired:
                                self._account(acc[j], k, up, n_in[j],
                                              counts[j], ops_lane[j],
                                              status[j][0], view.probe_ops)
                    usp.note(path="step" if need_step else "replay")

            # ------------------------------------------------------ finalize
            with obs.span("wave.finalize", self.metrics):
                self._finalize(jobs, retired, rows_d, valid_d, ovf, counts,
                               acc, plan, results, wave_no)
            wsp.note(retries=len(retries))
        return retries

    def _probe_cache(self, io, active: list[int], consts: np.ndarray,
                     cap: int, epoch: int, n_in: dict[int, int], rows_d,
                     valid_d, lane_layout: tuple
                     ) -> tuple[dict[int, tuple[str, object]],
                                dict[int, tuple]]:
        """The cache phase of one unit: digest-first canonicalization.

        The wave's device fingerprint (``stepper.digest_step``) is a pure
        function of each lane's valid prefix — byte-identical across
        lowerings and shard counts, so sharded waves hit fragments
        recorded by vmap waves and vice versa.  Returns each active
        lane's status (``miss``, ``hit`` with its entry, or ``shared``
        with the lane it aliases) and cache key."""
        status: dict[int, tuple[str, object]] = {}
        keys: dict[int, tuple] = {}
        with obs.span("cache.probe", self.metrics) as csp:
            d = np.asarray(stepper.digest_step(io.read_cols, *lane_layout)(
                rows_d, valid_d))
            self.metrics.host_syncs += 1
            first_of: dict[tuple, int] = {}
            for j in active:
                cvals = tuple(int(consts[j, i]) for i in io.const_idx)
                key = unit_digest_key(io, cvals, cap, epoch, n_in[j],
                                      tuple(int(x) for x in d[j]))
                keys[j] = key
                if key in first_of:
                    status[j] = ("shared", first_of[key])
                    self.cache.note_shared_hit()
                    continue
                entry = self.cache.get(key, epoch)
                if entry is None:
                    first_of[key] = j
                    status[j] = ("miss", None)
                else:
                    status[j] = ("hit", entry)
            if obs.tracer:
                csp.note(lanes=len(active),
                         hits=sum(1 for s, _ in status.values()
                                  if s != "miss"))
        return status, keys

    def _lower(self, up, view: _EpochView, jobs: list[_Job],
               active: list[int], k: int, cap: int, B: int, V: int,
               lowering: str, latch: bool):
        """The wave's jitted step for unit ``up`` under its lowering."""
        scfg = self.scfg
        if lowering == "shard":
            # latch waves merge at the full cap (global truncation must
            # see every shard's rows); non-latch waves trim to the
            # measured shard occupancy when the planner has observed this
            # unit, else the static skew headroom
            trim = cap if latch else \
                self._wave_shard_trim(jobs, active, k, cap)
            step = stepper.sharded_unit_step(
                up, self.store.radix, self.mesh, self.data_axis,
                self._shard_lane_axes, self._n_shards, view.logn,
                trim, latch, scfg.shard_merge)
            self.metrics.mesh_steps += 1
            self.metrics.shard_steps += 1
            # the per-unit merge collective's payload (rows incl. the
            # provenance column + validity), for the throughput model —
            # measured, not assumed.  Latch waves pay it once per branch
            # (mid-unit merges)
            rounds = len(up.branches) if latch else 1
            g_bytes = (B * self._n_shards * trim
                       * ((V + 1) * 4 + 1) * rounds)
            self.metrics.gather_bytes += g_bytes
            if scfg.shard_merge == "lexsort":
                self.metrics.merge_lexsort_steps += 1
            tr = obs.tracer
            if tr:
                tr.instant("gather.merge",
                           strategy=("lexsort"
                                     if scfg.shard_merge == "lexsort"
                                     else "kway"),
                           bytes=g_bytes, trim=trim, rounds=rounds)
            return step
        if lowering == "mesh":
            self.metrics.mesh_steps += 1
            return stepper.unit_step(up, self.store.radix, self.mesh,
                                     self._lane_axes, logn=view.logn)
        return stepper.unit_step(up, self.store.radix, logn=view.logn)

    def _insert(self, key: tuple, io, j: int, rows, src, n_out: int,
                overflow: bool, ops: int, peak: int, epoch: int) -> None:
        """Record a missed lane's replayable delta (one counted Omega
        block) and put the fragment in the cache.  ``rows`` and ``src``
        are the wave's output rows and provenance: host arrays from the
        step's one transfer, or, past ``WAVE_PULL_BYTES``, device arrays
        of which only this lane's prefix is pulled (two host syncs)."""
        with obs.span("cache.insert", self.metrics):
            self.metrics.host_block_pulls += 1
            if isinstance(rows, np.ndarray):
                out_rows, src_row = rows[j, :n_out], src[j, :n_out]
            else:
                self.metrics.host_syncs += 2
                out_rows = np.asarray(rows[j, :n_out])
                src_row = np.asarray(src[j, :n_out])
            # copies: an entry owns its bytes, where a view of the wave's
            # host arrays would keep the whole wave alive (and
            # ``bytes_stored`` would understate it)
            entry = FragmentEntry(
                src_row=np.array(src_row, order="C"),
                written=np.array(out_rows[:, list(io.write_cols)],
                                 order="C"),
                overflow=overflow,
                ops=ops,
                epoch=epoch,
                peak=peak,
            )
            self.cache.put(key, entry, epoch)

    def _account(self, a: _LaneAcc, k: int, up, n_in: int, n_out: int,
                 ops: int, status: str, probe_ops: int) -> None:
        """One lane's unit in its cost account (twin of
        ``engine._execute``); a cache-served unit's cost counts as saved."""
        nrs_d, ntb_d, server_d, client_d = stepper.unit_cost(
            self.cfg, k, up, n_in, n_out, ops, probe_ops)
        a.nrs += nrs_d
        a.ntb += ntb_d
        a.server += server_d
        a.client += client_d
        if status == "miss":
            a.misses += 1
        else:
            a.hits += 1
            a.nrs_saved += nrs_d
            a.ntb_saved += ntb_d

    def _finalize(self, jobs: list[_Job], retired: set[int], rows_d,
                  valid_d, ovf: np.ndarray, counts: list[int],
                  acc: list[_LaneAcc], plan: QueryPlan, results: dict,
                  wave_no: int) -> None:
        """Deliver the wave's finished jobs into ``results``.

        The one end-of-wave materialisation (two host syncs, deliberately
        not counted in ``host_block_pulls``, which tracks unit-stepping
        traffic); also where the planner observes each query's need."""
        tr = obs.tracer
        rows_h = np.asarray(rows_d)
        valid_h = np.asarray(valid_d)
        self.metrics.host_syncs += 2
        for j, job in enumerate(jobs):
            if j in retired:
                continue
            if self.scfg.cap_hints:
                if self.cfg.capacity_planner:
                    # record the query's true need (largest per-unit peak),
                    # not the cap it ran at — warm resubmissions then get
                    # right-sized tables even where the oracle overshot
                    self.planner.observe_query(
                        job.plan, self.cfg.max_cap if bool(ovf[j])
                        else self.planner.snug(job.peak_seen))
                elif job.cap != self.cfg.cap:
                    self._cap_hints[(job.plan.signature, job.consts)] = job.cap
            a = acc[j]
            n_results = counts[j]
            nrs, ntb = a.nrs, a.ntb
            if self.cfg.interface == "endpoint":
                nrs, ntb = stepper.endpoint_totals(self.cfg, n_results,
                                                   plan.n_vars)
                if plan.units and a.hits == len(plan.units):
                    # whole query served from cache: the one endpoint
                    # request never reaches the server
                    a.nrs_saved, a.ntb_saved = nrs, ntb
                else:
                    a.nrs_saved = a.ntb_saved = 0
            table = BindingTable(rows_h[j].copy(), valid_h[j].copy(),
                                 np.bool_(ovf[j]))
            stats = QueryStats(
                nrs=nrs, ntb=ntb, server_ops=a.server, client_ops=a.client,
                n_results=n_results, overflow=bool(ovf[j]),
                cache_hits=a.hits, cache_misses=a.misses,
                nrs_saved=a.nrs_saved, ntb_saved=a.ntb_saved,
            )
            results[job.rids[0]] = (table, stats)
            t1 = time.perf_counter() if obs.enabled else 0.0
            for rid in job.rids:
                # reap unconditionally: entries recorded while obs was on
                # must not leak if it is toggled off before the drain
                self._deadlines.pop(rid, None)
                t0 = self._t_submit.pop(rid, None)
                if obs.enabled:
                    if t0 is not None:
                        self.registry.observe("sched.query_latency_s",
                                              t1 - t0)
                    if tr:
                        tr.end_async("query", rid, n_results=n_results,
                                     wave=wave_no)
            if len(job.rids) > 1:
                # collapsed duplicates: whole response fanned out from the
                # shared execution — every unit request cache-served
                n_units = len(plan.units)
                self.cache.note_shared_hit(n_units * (len(job.rids) - 1))
                dup = stats._replace(cache_hits=n_units, cache_misses=0,
                                     nrs_saved=nrs, ntb_saved=ntb)
                for rid in job.rids[1:]:
                    results[rid] = (table, dup)


def _replay_deltas(live: dict[int, FragmentEntry], io, B: int, cap: int
                   ) -> tuple:
    """The replay step's uploads: each live lane's cached delta (source
    rows, written columns, row count) padded to a power-of-two width,
    which bounds the replay step's retraces."""
    n_w = len(io.write_cols)
    m = 1
    for e in live.values():
        m = max(m, e.n_out)
    m = min(1 << (m - 1).bit_length(), cap)
    src_h = np.zeros((B, m), np.int32)
    wr_h = np.zeros((B, m, n_w), np.int32)
    nout_h = np.zeros((B,), np.int32)  # non-hit lanes: empty
    for j, e in live.items():
        if e.n_out:
            src_h[j, :e.n_out] = e.src_row
            if n_w:
                wr_h[j, :e.n_out] = e.written
        nout_h[j] = e.n_out
    return jnp.asarray(src_h), jnp.asarray(wr_h), jnp.asarray(nout_h)
