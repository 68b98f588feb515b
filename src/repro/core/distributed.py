"""Distributed SPF runtime: the interface protocols as mesh collectives.

The paper's deployment is servers + clients over HTTP.  On a pod:

- the **server** is the set of devices along mesh axis ``data``, each holding
  a subject-hash shard of the triple store (``TripleStore.shard_by_subject``);
- each **client** is a query lane along mesh axis ``model`` (a batch of
  concurrent clients = the paper's 2^i-client configurations);
- a **request/response cycle** is one collective exchange along ``data``:
  the lane's current solution-mapping table Omega (replicated over ``data``)
  seeds local evaluation on every shard, and shard-local results are
  ``all_gather``-ed back to the lane.

Because star-pattern matches share a subject and the store is subject-hash
sharded, *server-side star joins never communicate* — only star-level
results cross the network.  TPF/brTPF-granularity engines must gather after
every triple pattern instead, so their collective schedule is strictly
larger: this module makes the paper's NTB/NRS claims *measurable in HLO*
(see launch/roofline.py which parses the lowered collectives).

The multi-pod mesh adds a ``pod`` axis that replicates the store (the
paper's availability argument) and splits the client population.

``eval_unit`` runs here inside ``shard_map`` + ``vmap`` (one trace per
shard, vmapped over query lanes), so every probe primitive it dispatches
through ``repro.kernels.ops`` must be shard_map/vmap-compatible: the
Pallas kernels batch by grid extension and the jnp oracles are pure
element-wise/scan code, so the same engine code lowers under both
``ops.FORCE`` settings (see ``DistributedEngine.lower_step``).

The batching scaffold itself is the module-level ``make_batch_step``
factory: ``mesh=None`` yields the single-host ``jit(vmap(...))`` step the
concurrent scheduler (``core/scheduler.py``) dispatches its buckets
through; with a mesh it yields the ``shard_map`` step used here.  Since
PR 5 the per-unit collective itself (local evaluation + order-restoring
gather) lives in ``core/stepper.py`` (``eval_unit_sharded`` +
``gather_merge``), shared between this module's whole-query lane and the
scheduler's sharded wave steps — one lane evaluator, and the serial loop,
vmap waves, replicated mesh waves, sharded mesh waves and this whole-query
sharded lane are all instantiations of it.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.compat import auto_axes
from repro.compat import shard_map as _shard_map

from repro.core.bindings import BindingTable, unit_table
from repro.core.capacity import CapacityPlanner
from repro.core.engine import EngineConfig, QueryPlan, plan_query
from repro.core.fragcache import FragmentCache
from repro.core.patterns import BGP
from repro.rdf.store import StoreArrays, TripleStore


class DistStats(NamedTuple):
    """Per-lane traffic account (analytic, device scalars)."""

    rounds: jnp.ndarray  # collective rounds (the NRS analogue)
    gathered_rows: jnp.ndarray  # rows crossing the network (NTB analogue)
    gathered_bytes: jnp.ndarray
    server_ops: jnp.ndarray
    n_results: jnp.ndarray
    overflow: jnp.ndarray


@dataclass(frozen=True)
class DistConfig:
    cap: int = 2048  # per-lane table capacity
    shard_cap: int = 1024  # per-shard local result capacity
    data_axis: str = "data"
    model_axis: str = "model"
    pod_axis: str | None = None  # set for the multi-pod mesh
    # beyond-paper optimisation (EXPERIMENTS.md §Perf): when a unit's
    # subject is already bound, each Omega row can only match on the shard
    # its subject hashes to — mask the other shards' evaluation instead of
    # probing redundantly everywhere (server work / HBM reads ~ /n_shards)
    owner_masking: bool = False


def make_batch_step(lane_fn, out_proto=None, *, mesh: Mesh | None = None,
                    data_axis: str | None = "data",
                    lane_axes: tuple[str, ...] = ("model",),
                    name: str = "step"):
    """Lift a per-lane evaluator into one jitted batch step (the shared
    step factory behind both engines and the scheduler's mesh waves).

    ``lane_fn(dev: StoreArrays, *lane_args) -> pytree`` evaluates a single
    query lane against one store replica/shard.  The returned step takes
    ``(store_arrays, *batched_lane_args)`` with a leading batch axis on
    every lane arg:

    - ``mesh=None`` — single host: ``jit(vmap(lane_fn))`` with the store
      broadcast.  This is the scheduler's narrow-wave step
      (``core/scheduler.py``): plan-homogeneity is the scheduler's internal
      bucketing detail, and batching is plain ``vmap``.
    - ``mesh`` given, ``data_axis`` set — the sharded-store distributed
      step: ``shard_map`` with the store sharded along ``data_axis``
      (leading shard axis on every array) and lanes along ``lane_axes``,
      the same ``vmap`` inside each shard.
    - ``mesh`` given, ``data_axis=None`` — the replicated-store mesh step:
      the store (no shard axis) is broadcast to every device and only the
      lane batch splits along ``lane_axes``.  Lane results are then
      byte-identical to the ``mesh=None`` lowering — this is how the
      scheduler routes wide waves across mesh lanes without giving up its
      serial-parity contract (a subject-hash shard would reorder rows).

    In the mesh cases ``out_proto`` must mirror the lane output pytree
    structure (leaf values are ignored) so the factory can derive
    ``shard_map`` out_specs.  Either way the lane evaluator is written
    once and lowers under all three — the collective schedule (or its
    absence) is the only difference.  ``name`` names the jitted program
    (``jit_<name>`` in the profiler's trace).
    """
    if mesh is None:
        def step(dev: StoreArrays, *lane_args):
            in_axes = (None,) + (0,) * len(lane_args)
            return jax.vmap(lane_fn, in_axes=in_axes)(dev, *lane_args)

        return jax.jit(_named(step, name))

    if out_proto is None:
        raise ValueError("mesh-mapped steps need out_proto for out_specs")
    store_spec = StoreArrays(*[P(data_axis) if data_axis else P()
                               for _ in StoreArrays._fields])
    # an empty lane_axes (every mesh axis shards the store) replicates the
    # lane batch across the mesh — each shard evaluates all lanes locally
    lane_spec = P() if not lane_axes else \
        P(lane_axes if len(lane_axes) > 1 else lane_axes[0])
    out_specs = jax.tree_util.tree_map(lambda _: lane_spec, out_proto)

    def step(stacked: StoreArrays, *lane_batches):
        def shard_fn(dev: StoreArrays, *lanes_local):
            if data_axis:
                dev = StoreArrays(*[a[0] for a in dev])  # drop shard axis
            return jax.vmap(lambda *la: lane_fn(dev, *la))(*lanes_local)

        in_specs = (store_spec,) + (lane_spec,) * len(lane_batches)
        return _shard_map(shard_fn, mesh, in_specs, out_specs)(
            stacked, *lane_batches)

    return jax.jit(_named(step, name))


def _named(fn, name: str):
    """``fn`` renamed, so that its jitted program is ``jit_<name>``."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _lane_eval(plans: tuple, n_vars: int, cfg: DistConfig, radix: int,
               interface: str, n_shards: int, logn: int, dev: StoreArrays,
               const_vec: jnp.ndarray
               ) -> tuple[jnp.ndarray, jnp.ndarray, DistStats]:
    """Evaluate one query lane against the local shard, gathering along
    ``data`` after every unit.  Runs *inside* shard_map.

    ``dev`` is the local shard's index arrays; ``const_vec`` the lane's
    constants; ``n_shards`` the static ``data``-axis extent (shapes depend
    on it, so it is threaded in from the mesh rather than read off the
    axis environment); ``logn`` the *global* store's log-factor (the cost
    account must match the serial engine's, and the local shard's length
    would drift from it).  Returns (rows, valid, stats); rows/valid are
    the lane's final table (replicated along ``data``).

    Since PR 5 this is just the whole-query instantiation of the shared
    sharded unit machinery (``stepper.eval_unit_sharded`` +
    ``stepper.gather_merge``): local collective-free unit evaluation, one
    order-restoring gather per unit.  The gather sorts by provenance +
    drawn-value columns, so lane results are *byte-identical* to the
    serial engine — not merely set-equal as before — and ``server_ops``
    is the exact serial account (rebuilt from scalar psums of the
    branch-boundary counts).
    """
    from repro.core import stepper
    from repro.core.server import unit_io

    axis = cfg.data_axis
    width = max(n_vars, 1)
    table = unit_table(cfg.cap, width)
    rounds = jnp.int64(0)
    g_rows = jnp.int64(0)
    g_bytes = jnp.int64(0)
    server_ops = jnp.int64(0)
    overflow = table.overflow

    my_shard = jax.lax.axis_index(axis)
    # owner masking still routes bound-subject probes through
    # kops.eqrange_owned (fewer index reads on real hardware); results are
    # identical either way — a non-owner shard's runs are empty regardless,
    # because the data simply is not there
    owner = (my_shard, n_shards) if cfg.owner_masking else None
    trim = min(cfg.shard_cap, cfg.cap)
    # k-way merge on power-of-two shard counts, replicated lexsort
    # otherwise — bit-identical either way (stepper.select_gather_merge)
    merge_fn = stepper.select_gather_merge("auto", n_shards)
    for up in plans:
        # --- server side: local (collective-free) unit evaluation ---------
        prov = jnp.arange(cfg.cap, dtype=jnp.int32)[:, None]
        seeded = BindingTable(jnp.concatenate([table.rows, prov], axis=1),
                              table.valid, overflow)
        local, ops, _, cnt, ovf = stepper.eval_unit_sharded(
            dev, radix, up, const_vec, seeded, axis=axis, logn=logn,
            owner=owner)
        server_ops = server_ops + ops

        # --- network: shard-local results -> client lane (one collective,
        # order-restoring: provenance column + drawn-value columns) --------
        sort_cols = (width,) + tuple(unit_io(up).write_cols)
        rows_m, valid_m, lost = merge_fn(
            local.rows, local.valid, sort_cols, axis, cfg.cap, trim)
        overflow = ovf | (jax.lax.psum(lost.astype(jnp.int32), axis) > 0)
        table = BindingTable(rows_m[:, :-1], valid_m, overflow)

        rounds = rounds + 1
        g_rows = g_rows + cnt
        # bytes actually moved by the all_gather (rows incl. the provenance
        # column, plus the validity mask, from every shard)
        g_bytes = g_bytes + n_shards * trim * ((width + 1) * 4 + 1)

    stats = DistStats(
        rounds=rounds,
        gathered_rows=g_rows,
        gathered_bytes=g_bytes,
        server_ops=server_ops,
        n_results=table.count(),
        overflow=overflow,
    )
    return table.rows, table.valid, stats


class DistributedEngine:
    """Batched multi-device query engine for one interface granularity.

    A *step* evaluates a batch of structurally identical queries (one plan
    signature), one lane per ``model``-axis (x ``pod``-axis) slot.  This is
    the unit the dry-run lowers and the roofline analyses: its collective
    schedule IS the interface's network behaviour.
    """

    def __init__(self, store: TripleStore, mesh: Mesh,
                 cfg: EngineConfig, dcfg: DistConfig | None = None):
        self.store = store
        self.mesh = mesh = auto_axes(mesh)
        self.cfg = cfg
        self.dcfg = dcfg or DistConfig()
        if self.dcfg.pod_axis and self.dcfg.pod_axis not in mesh.axis_names:
            self.dcfg = replace(self.dcfg, pod_axis=None)
        self._n_data = mesh.shape[self.dcfg.data_axis]
        self._stacked_cache: StoreArrays | None = None
        self._stacked_epoch = store.epoch
        self._cache: dict = {}
        # the pod's shared star-fragment cache: every scheduler this engine
        # spawns (run_load) consults the same epoch-tagged host-side cache,
        # so a fragment computed for one wave serves every later lane on
        # the pod until the store epoch moves past it
        self.pod_cache = FragmentCache()
        # ...and the pod's shared capacity planner: high-water marks
        # observed by any scheduler on the pod size every later request's
        # tables (epoch-tagged like the cache; core/capacity.py)
        self.pod_planner = CapacityPlanner(store, cfg)
        # run_load's default scheduler, kept across calls so repeated
        # loads reuse its sharded store arrays and step caches
        self._load_sched = None

    @property
    def _stacked(self) -> StoreArrays:
        """Sharded-store arrays, built lazily (dry-run never materialises)
        and versioned by the store epoch: a ``bump_epoch`` after a store
        mutation forces a re-shard, so the engine can never keep serving
        pre-mutation arrays (and then poison the pod cache under the new
        epoch)."""
        if self._stacked_cache is None \
                or self._stacked_epoch != self.store.epoch:
            self._stacked_cache = self.store.stacked_shard_arrays(self._n_data)
            self._stacked_epoch = self.store.epoch
        return self._stacked_cache

    # -------------------------------------------------------------- planning
    def plan_batch(self, queries: list[BGP]) -> tuple[QueryPlan, np.ndarray]:
        """Plan a batch; all queries must share the plan signature."""
        plans = [plan_query(self.store, q, self.cfg) for q in queries]
        sig = plans[0].signature
        for p in plans[1:]:
            if p.signature != sig:
                raise ValueError(
                    "plan_batch requires a plan-homogeneous batch; "
                    "run_batch buckets mixed batches by signature itself "
                    "(as does the single-host scheduler, core/scheduler.py)")
        consts = np.stack([np.asarray(p.consts, np.int64) for p in plans])
        return plans[0], consts

    def group_by_signature(self, queries: list[BGP]) -> dict[tuple, list[BGP]]:
        groups: dict[tuple, list[BGP]] = {}
        for q in queries:
            sig = plan_query(self.store, q, self.cfg).signature
            groups.setdefault(sig, []).append(q)
        return groups

    def _lane_slots(self) -> tuple[tuple[str, ...], int]:
        """Lane mesh axes and the total lane-slot count they provide."""
        dcfg = self.dcfg
        lane_axes = (dcfg.pod_axis, dcfg.model_axis) if dcfg.pod_axis \
            else (dcfg.model_axis,)
        slots = 1
        for a in lane_axes:
            slots *= self.mesh.shape[a]
        return lane_axes, slots

    # -------------------------------------------------------------- execution
    def make_step(self, plan: QueryPlan, batch: int):
        """Build the jitted shard_map step for ``batch`` query lanes
        (the mesh instantiation of the shared ``make_batch_step`` factory)."""
        dcfg = self.dcfg
        lane_axes, n_lane_slots = self._lane_slots()
        if batch % n_lane_slots:
            raise ValueError(f"batch {batch} not divisible by lane slots "
                             f"{n_lane_slots}")
        per_lane = batch // n_lane_slots

        from repro.core.server import log_factor
        logn = log_factor(self.store.n_triples)  # GLOBAL store's factor

        def lane_fn(dev, const_vec):
            return _lane_eval(plan.units, plan.n_vars, dcfg, self.store.radix,
                              plan.interface, self._n_data, logn, dev,
                              const_vec)

        step = make_batch_step(
            lane_fn, out_proto=(0, 0, DistStats(*[0] * 6)), mesh=self.mesh,
            data_axis=dcfg.data_axis, lane_axes=lane_axes)
        return step, per_lane

    def run_batch(self, queries: list[BGP]):
        """Evaluate a batch of queries, one lane each.

        Plan-homogeneous batches run as a single step and return stacked
        ``(rows, valid, stats)`` arrays (the paper's concurrent-client
        configuration).  Mixed batches are bucketed by plan signature
        internally — each bucket padded to a lane-slot multiple with
        duplicate lanes and run as its own step — and return per-query
        *lists* in input order (entries of different signatures have
        different widths, so there is no single stacked array to return).
        """
        groups: dict[tuple, list[int]] = {}
        plans = [plan_query(self.store, q, self.cfg) for q in queries]
        for i, p in enumerate(plans):
            groups.setdefault(p.signature, []).append(i)
        if len(groups) == 1:
            consts = np.stack([np.asarray(p.consts, np.int64) for p in plans])
            step, _ = self._get_step(plans[0], consts.shape[0])
            rows, valid, stats = step(self._stacked, jnp.asarray(consts))
            return rows, valid, stats

        out: dict[int, tuple] = {}
        _, slots = self._lane_slots()
        for idxs in groups.values():
            plan = plans[idxs[0]]
            consts = np.stack([np.asarray(plans[i].consts, np.int64)
                               for i in idxs])
            # pad the bucket to a lane-slot multiple with duplicate lanes
            pad = -len(idxs) % slots
            if pad:
                consts = np.concatenate(
                    [consts, np.repeat(consts[:1], pad, axis=0)])
            step, _ = self._get_step(plan, consts.shape[0])
            rows, valid, stats = step(self._stacked, jnp.asarray(consts))
            for lane, i in enumerate(idxs):
                out[i] = (rows[lane], valid[lane],
                          jax.tree_util.tree_map(lambda a: a[lane], stats))
        ordered = [out[i] for i in range(len(queries))]
        return ([r for r, _, _ in ordered], [v for _, v, _ in ordered],
                [s for _, _, s in ordered])

    def _get_step(self, plan: QueryPlan, batch: int):
        # the store epoch is part of the key: make_step bakes the *logical*
        # triple count's log-factor into the lane closure, and a
        # tombstone-only delta changes it without changing any array shape
        key = (plan.signature, batch, self.store.epoch)
        if key not in self._cache:
            self._cache[key] = self.make_step(plan, batch)
        return self._cache[key]

    def run_load(self, queries: list[BGP], scheduler=None):
        """Serve a query list through a mesh-routed concurrent scheduler.

        The distributed counterpart of ``QueryEngine.run_load``: requests
        are bucketed by plan signature and stepped unit-by-unit, and the
        scheduler picks each wave's lowering from this engine's mesh.
        When the mesh carries the engine's ``data`` axis, wide waves run
        **sharded**: the store is subject-hash sharded along it (the same
        per-device memory footprint as ``run_batch`` — 1/n_data of the
        index per device) and wave lanes span the remaining axes, with one
        order-restoring collective per unit
        (``stepper.sharded_unit_step``).  Narrow waves fall back to
        replicated mesh lanes or single-host vmap.  All waves share
        ``self.pod_cache`` and ``self.pod_planner``, so fragments and
        high-water marks observed anywhere on the pod serve every later
        request.  Results and gross stats are byte-identical to the serial
        ``QueryEngine.run`` path — the lowering changes placement, never
        the computation.

        Pass a ``QueryScheduler`` to control the configuration or reuse
        metrics across calls; it must have been built with
        ``cache=engine.pod_cache`` to keep the pod-shared contract.
        Without one, the engine keeps a default scheduler across calls so
        repeated loads reuse its sharded store arrays and step caches.
        """
        from repro.core.scheduler import QueryScheduler

        if scheduler is not None:
            return scheduler.run_queries(queries)
        # QueryScheduler raises its wave-width cap to the mesh's slot
        # count itself, so the default config spans any pod width
        if getattr(self, "_load_sched", None) is None \
                or self._load_sched.mesh is not self.mesh:
            self._load_sched = QueryScheduler(
                self.store, self.cfg, cache=self.pod_cache, mesh=self.mesh,
                planner=self.pod_planner, data_axis=self.dcfg.data_axis)
        return self._load_sched.run_queries(queries)

    # ---------------------------------------------------------------- dry-run
    def lower_step(self, plan: QueryPlan, batch: int,
                   shard_len: int | None = None):
        """Lower + compile the step for dry-run / roofline analysis.

        ``shard_len`` overrides the per-shard triple count so the production
        mesh can be dry-run without materialising a sharded store (shapes
        only, ShapeDtypeStruct stand-ins).
        """
        step, _ = self.make_step(plan, batch)
        n_consts = len(plan.consts)
        if shard_len is None:
            shard_len = -(-self.store.n_triples // self._n_data) + 64
        D = self._n_data
        ds = NamedSharding(self.mesh, P(self.dcfg.data_axis))

        def _spec(length, dtype):
            return jax.ShapeDtypeStruct((D, length), dtype, sharding=ds)

        # dry-run lowers the no-delta fast path: zero-length delta arrays
        # are the trace-time static the production store also presents
        # when it has no pending writes
        stacked_spec = StoreArrays(
            key_ps_pso=_spec(shard_len, jnp.int32),
            s_pso=_spec(shard_len, jnp.int32),
            o_pso=_spec(shard_len, jnp.int32),
            key_po_pos=_spec(shard_len, jnp.int32),
            s_pos=_spec(shard_len, jnp.int32),
            o_pos=_spec(shard_len, jnp.int32),
            ins_key_ps=_spec(0, jnp.int32),
            ins_s_pso=_spec(0, jnp.int32),
            ins_o_pso=_spec(0, jnp.int32),
            ins_key_po=_spec(0, jnp.int32),
            ins_s_pos=_spec(0, jnp.int32),
            ins_o_pos=_spec(0, jnp.int32),
            tomb_pos_ps=_spec(0, jnp.int32),
            tomb_adj_ps=_spec(0, jnp.int32),
            tomb_pos_po=_spec(0, jnp.int32),
            tomb_adj_po=_spec(0, jnp.int32),
        )
        lane_axes, _ = self._lane_slots()
        const_spec = jax.ShapeDtypeStruct(
            (batch, n_consts), jnp.int64,
            sharding=NamedSharding(
                self.mesh,
                P(lane_axes if len(lane_axes) > 1 else lane_axes[0])))
        return step.lower(stacked_spec, const_spec)
