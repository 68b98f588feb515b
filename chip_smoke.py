#!/usr/bin/env python3
"""Bring-up smoke of the SPF served path on a TPU.

One process drives the path a SPARQL client uses: SPARQL text ->
``endpoint.parse`` -> ``EndpointService`` -> ``QueryScheduler`` waves ->
fragment cache -> Pallas kernels, over the paper's 10M-triple WatDiv
instance (``configs.spf_watdiv.FULL_GRAPH``, generated from its seed and
indexed on the device).

    python chip_smoke.py             # one chip: serve, ingest, serve again
    python chip_smoke.py --chips 4   # four chips: sharded waves vs serial

One chip: 8 clients each send 3 distinct queries of the ``union`` and
``2-stars`` loads, served four times through ``EndpointService``: cold on
the default scheduler (capacity planner on); cold and warm on one without
cap hints or duplicate collapsing, so that several lanes of a wave are
live, and the warm pass replays on the device (the only unit steps it
may run are rungs that overflow again before a retry); and on the
default one again after one insert/delete batch lands through
``EndpointService.ingest`` (merged base+delta probes).
Every response must be ``"ok"`` and byte-identical to a serial
``QueryEngine`` run under ``ops.FORCE = "ref"`` (the jnp oracles) on the
same store epoch, the Pallas path must have dispatched ``eqrange``,
``run_probe``, ``fingerprint_rows``, ``replay_delta`` and
``delta_probe``, and no call may have fallen back to the oracle
(``breaker_ref``).

Four chips: the store is sharded by subject hash over a ``(4, 1)``
``data x model`` mesh, and the first fitting query of each load (not
``PER_LOAD``: four chips cost four times as much per second) is served to
the clients in sharded waves;
rows must be byte-identical to the serial ``FORCE="ref"`` run on one
device.  The first query with a bound-subject unit (one is always
picked) also runs as an owner-masked ``DistributedEngine`` batch on that
mesh, whose bound-subject probes go through the ``owned_probe`` kernel,
with the same rows.  ``eqrange``, ``eqrange_owned`` and ``run_probe`` must have
dispatched Pallas, and none may have fallen back.

The smoke serves the first ``PER_LOAD`` queries of each load, among its
first ``CANDIDATES``, that run without overflowing a ``MAX_CAP``-row
binding table in the serial reference run (plus the first one with a
bound-subject unit, if none of those has one): the probe kernels stream
the whole key column past every table tile, so a probe's cost grows with
rows x column.

Earlier lines report sizes, walls, compile time, dispatch counts and peak
device memory — bring-up facts, not benchmark numbers.  The last line is
``{"ok": true, "device": {...}}``; any failed phase exits non-zero and
prints no such line.  Without a TPU it exits non-zero at once.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

LOADS = ("union", "2-stars")
CLIENTS = 8  # concurrent SPARQL clients
PER_CLIENT = 3  # distinct queries each client sends
CANDIDATES = 32  # queries generated per load to choose from
PER_LOAD = 3  # queries served per load: the first candidates that fit
# four chips cost four times as much per second: the sharded comparison
# takes one query per load (plus a bound-subject one), compiling a third
# as many programs
FOUR_CHIP_PER_LOAD = 1
MAX_CAP = 1 << 14  # largest binding table (rows) a served query may need
# primitives whose Pallas path each mode must dispatch
ONE_CHIP_PRIMS = ("eqrange", "run_probe", "fingerprint_rows", "replay_delta",
                  "delta_probe")
FOUR_CHIP_PRIMS = ("eqrange", "eqrange_owned", "run_probe")


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(**kw) -> None:
    print(json.dumps(kw, default=str), flush=True)


class CompileClock:
    """Seconds JAX spent compiling, and its persistent-cache hits/misses."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.hits = self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.secs += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def read(self) -> dict:
        return {"compile_s": self.secs, "cache_hits": self.hits,
                "cache_misses": self.misses}


def build_store(seed: int):
    import jax

    from repro.configs.spf_watdiv import FULL_GRAPH
    from repro.rdf.store import TripleStore
    from repro.rdf.watdiv import generate_watdiv

    t0 = time.perf_counter()
    g = generate_watdiv(replace(FULL_GRAPH, seed=seed))
    store = TripleStore.build(g.s, g.p, g.o, n_terms=g.n_terms,
                              n_predicates=g.n_predicates)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    arrays = jax.block_until_ready(store.device)
    say(phase="store", triples=store.n_triples, terms=store.n_terms,
        predicates=store.n_predicates, build_s=build_s,
        upload_s=time.perf_counter() - t0,
        store_device_bytes=int(sum(a.nbytes for a in arrays)))
    return g, store


def engine_config():
    """The SPF engine, with overflow given up at ``MAX_CAP`` rows: a query
    that fits returns the same rows at any larger ``max_cap``."""
    from repro.core.engine import EngineConfig

    return EngineConfig(interface="spf", max_cap=MAX_CAP)


def reference(store):
    """A serial ``QueryEngine`` run under ``FORCE="ref"`` (the jnp
    oracles): ``run(query) -> (rows, stats)``.  ``FORCE`` is read at trace
    time, and the step caches key on it, so every run sets it."""
    from repro.core.engine import QueryEngine, results_as_numpy
    from repro.kernels import ops

    eng = QueryEngine(store, engine_config())

    def run(query):
        old = ops.FORCE
        ops.FORCE = "ref"
        try:
            table, stats = eng.run(query)
        finally:
            ops.FORCE = old
        return results_as_numpy(table), stats
    return run


def bound_subject(store, query) -> bool:
    """Whether a unit of ``query``'s plan starts with its subject bound (a
    probe branch): the units owner masking sends through ``owned_probe``."""
    from repro.core.engine import plan_query

    plan = plan_query(store, query, engine_config())
    return any(u.branches[0].case.startswith("probe") for u in plan.units)


def pick_queries(g, store, clock, per_load: int):
    """The first ``per_load`` queries of each load, among its first
    ``CANDIDATES``, whose serial reference run fits ``MAX_CAP`` rows; if
    none of them has a bound-subject unit, also the first remaining
    candidate that has one and fits.  Returns (queries, reference rows,
    bound-subject flags).  The probe kernels stream the whole key column
    past every table tile, so a probe's cost grows with rows x column:
    larger tables are left to the benchmark."""
    from repro.rdf.queries import QueryLoadConfig, generate_query_load

    t0 = time.perf_counter()
    run = reference(store)
    qs, want, rest = [], [], []
    for load in LOADS:
        cand = generate_query_load(g, store, load,
                                   QueryLoadConfig(n_queries=CANDIDATES))
        fits = []
        for i, q in enumerate(cand):
            if len(fits) == per_load:
                rest += [(load, j, c) for j, c in enumerate(cand) if j >= i]
                break
            rows, st = run(q)
            if not bool(st.overflow):
                fits.append(i)
                qs.append(q)
                want.append(rows)
        say(phase="select", load=load, picked=fits,
            rows=[int(r.shape[0]) for r in want[len(want) - len(fits):]])
        check(len(fits) >= min(2, per_load),
              f"{load}: only {len(fits)} of {len(cand)} candidates fit "
              f"{MAX_CAP} rows")
    bound = [bound_subject(store, q) for q in qs]
    for load, i, q in ([] if any(bound) else rest):
        if bound_subject(store, q):
            rows, st = run(q)
            if not bool(st.overflow):
                say(phase="select", load=load, bound_subject=i,
                    rows=int(rows.shape[0]))
                qs.append(q)
                want.append(rows)
                bound.append(True)
                break
    check(any(bound), "no candidate with a bound-subject unit fits")
    say(phase="reference", queries=len(qs), bound_subject=sum(bound),
        wall_s=time.perf_counter() - t0, **clock.read())
    return qs, want, bound


def client_queries(n: int) -> list[list[int]]:
    """Per client, the indices of the ``PER_CLIENT`` distinct queries it
    sends: consecutive clients take consecutive slices of the picked
    queries (wrapping); with more queries than ``PER_CLIENT``, no two
    neighbours send the same set."""
    return [[(c * PER_CLIENT + k) % n for k in range(min(PER_CLIENT, n))]
            for c in range(CLIENTS)]


def check_dispatch(prims) -> None:
    """Kernel dispatch tallies (obs on): every primitive in ``prims``
    dispatched its Pallas path, and no call fell back to the oracle."""
    from repro import obs
    from repro.kernels import ops

    snap = obs.registry.snapshot()
    counts = {k[len("kernels.dispatch."):]: int(v) for k, v in snap.items()
              if k.startswith("kernels.dispatch.")}
    say(phase="dispatch", counts=counts, breaker=ops.BREAKER.snapshot())
    fallbacks = {k: v for k, v in counts.items()
                 if k.endswith(".breaker_ref")}
    check(not fallbacks, f"kernel fallbacks to the oracle: {fallbacks}")
    for prim in prims:
        n = sum(counts.get(f"{prim}.{path}", 0)
                for path in ("pallas", "prefetch", "dense"))
        check(n > 0, f"the Pallas path never dispatched {prim}")


PASS_METRICS = ("steps", "retries", "lane_steps", "active_lane_steps")


def serve_and_check(svc, texts, want, tag: str) -> dict:
    """Serve each client's queries through ``svc``; every response must be
    ``ok`` and byte-identical to ``want``.  Returns the pass's scheduler
    tallies (``PASS_METRICS``)."""
    from repro.endpoint.service import EndpointRequest

    sched = svc.sched
    before = {k: int(getattr(sched.metrics, k)) for k in PASS_METRICS}
    reqs = [EndpointRequest(client=c, sparql=texts[i])
            for c, idx in enumerate(client_queries(len(texts)))
            for i in idx]
    t0 = time.perf_counter()
    resps = svc.serve(reqs)
    wall = time.perf_counter() - t0
    bad = [(r.status, r.error) for r in resps if r.status != "ok"]
    check(not bad, f"{tag}: {len(bad)} responses not ok, e.g. {bad[:2]}")
    for r, req in zip(resps, reqs):
        check(r.rows.tobytes() == want[req.sparql].tobytes(),
              f"{tag}: rows differ from the FORCE='ref' serial engine")
    tally = {k: int(getattr(sched.metrics, k)) - v
             for k, v in before.items()}
    say(phase=tag, requests=len(reqs), distinct=len(set(texts)),
        serve_s=wall, cache_hits=int(sched.cache.stats.hits), **tally)
    return tally


def ingest_batch(store, query, rng):
    """A few deletes of live triples and inserts of new ones, on the
    predicates of ``query`` (so its units miss the cache and evaluate
    with the delta overlaid)."""
    import numpy as np

    preds = sorted({t.p.id for t in query.patterns if not t.p.is_var})
    s_all, p_all, o_all = store.merged_triples()
    pool = np.nonzero(np.isin(p_all, preds))[0]
    dele = rng.choice(pool, 8, replace=False)
    ins = rng.choice(pool, 8, replace=False)
    new_s = rng.integers(0, store.n_terms, 8)
    return dict(delete=(s_all[dele], p_all[dele], o_all[dele]),
                insert=(new_s, p_all[ins], o_all[ins]))


def one_chip(seed: int, dev, clock) -> None:
    """Serve cold on the default scheduler (capacity planner on), cold and
    warm on one without cap hints or duplicate collapsing (the warm pass
    replays on the device), then again on the default one after an
    ingest batch."""
    import numpy as np

    from repro import obs
    from repro.core.scheduler import QueryScheduler, SchedulerConfig
    from repro.endpoint import to_sparql
    from repro.endpoint.service import EndpointService, ServiceConfig

    g, store = build_store(seed)
    obs.enable(trace=False)  # registry only: dispatch tallies, no tracer
    qs, rows, _ = pick_queries(g, store, clock, PER_LOAD)
    texts = [to_sparql(q) for q in qs]
    want = dict(zip(texts, rows))

    def service(sched_cfg):
        sched = QueryScheduler(store, engine_config(), sched_cfg)
        return EndpointService(sched, ServiceConfig(
            max_inflight_per_client=PER_CLIENT))

    planned = service(SchedulerConfig())
    c0 = clock.read()["compile_s"]
    serve_and_check(planned, texts, want, "serve_planned")
    # cap hints off: a request's cache key does not move between passes,
    # so the warm pass's waves are all-hit and replay on the device;
    # duplicates not collapsed: the clients that send one query fill
    # several lanes of its waves
    replay = service(SchedulerConfig(cap_hints=False,
                                     collapse_duplicates=False))
    cold = serve_and_check(replay, texts, want, "serve_cold")
    check(cold["active_lane_steps"] > cold["steps"],
          "no wave of the cold pass ran more than one live lane")
    c1 = clock.read()["compile_s"]
    warm = serve_and_check(replay, texts, want, "serve_warm")
    # the only steps a warm pass runs are rungs that overflow again (the
    # cache never holds an overflowed table); each is followed by a retry
    check(warm["steps"] <= warm["retries"],
          f"the warm pass ran {warm['steps']} unit steps for "
          f"{warm['retries']} overflow retries instead of replaying")
    say(phase="compile", cold_passes_compile_s=c1 - c0,
        warm_pass_compile_s=clock.read()["compile_s"] - c1)

    epoch = planned.ingest(**ingest_batch(store, qs[0],
                                          np.random.default_rng(seed)))
    say(phase="ingest", epoch=epoch, delta_size=store.delta_size)
    check(store.delta_size > 0, "the ingest batch changed nothing")
    run = reference(store)
    post = [run(q) for q in qs]
    check(not any(bool(st.overflow) for _, st in post),
          "a served query outgrew MAX_CAP after the ingest")
    want = {t: r for t, (r, _) in zip(texts, post)}
    serve_and_check(planned, texts, want, "serve_after_ingest")

    check_dispatch(ONE_CHIP_PRIMS)
    say(phase="memory", **{k: v for k, v in (dev.memory_stats() or {}).items()
                           if k in ("peak_bytes_in_use", "bytes_limit")})


def four_chips(seed: int, clock) -> None:
    """Sharded scheduler waves over a ``(4, 1)`` data x model mesh, and
    the first query with a bound-subject unit as an owner-masked
    ``DistributedEngine`` batch on the same mesh, vs the serial
    ``FORCE="ref"`` engine on one device."""
    import jax
    import numpy as np

    from repro import obs
    from repro.core.distributed import DistConfig, DistributedEngine
    from repro.core.engine import results_as_numpy
    from repro.core.scheduler import QueryScheduler, SchedulerConfig

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, found "
                                   f"{len(jax.devices())}")
    g, store = build_store(seed)
    obs.enable(trace=False)
    qs, want, bound = pick_queries(g, store, clock, FOUR_CHIP_PER_LOAD)

    mesh = jax.make_mesh((4, 1), ("data", "model"))
    sched = QueryScheduler(store, engine_config(), SchedulerConfig(
        collapse_duplicates=False), mesh=mesh, data_axis="data")
    stream = [(c, i) for c, idx in enumerate(client_queries(len(qs)))
              for i in idx]
    t0 = time.perf_counter()
    out = sched.serve([(c, qs[i]) for c, i in stream])
    wall = time.perf_counter() - t0
    for (table, _), (_, i) in zip(out, stream):
        check(results_as_numpy(table).tobytes() == want[i].tobytes(),
              "sharded rows differ from the serial run")
    m = sched.metrics
    say(phase="sharded", requests=len(stream), serve_s=wall,
        steps=int(m.steps), shard_steps=int(m.shard_steps),
        gather_bytes=int(m.gather_bytes), **clock.read())
    check(m.shard_steps > 0, "no wave ran on the sharded store")

    # owner masking: a unit that starts with its subject bound probes
    # through the owned_probe kernel, and the shards that do not own a
    # row's subject give it an empty run
    eng = DistributedEngine(store, mesh, engine_config(), DistConfig(
        cap=MAX_CAP, shard_cap=MAX_CAP, owner_masking=True))
    t0 = time.perf_counter()
    i = bound.index(True)
    rows, valid, stats = eng.run_batch([qs[i]])
    check(not bool(np.asarray(stats.overflow)[0]),
          "the owner-masked lane overflowed")
    check(np.asarray(rows[0])[np.asarray(valid[0])].tobytes()
          == want[i].tobytes(), "owner-masked rows differ from the serial run")
    say(phase="owner_masked", query=i, rows=int(want[i].shape[0]),
        wall_s=time.perf_counter() - t0, **clock.read())
    check_dispatch(FOUR_CHIP_PRIMS)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=7,
                    help="WatDiv generator seed (data is made anew)")
    args = ap.parse_args()
    try:
        import jax

        from repro.chip import enable_compile_cache, require_tpu
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package from "
              f"{Path(__file__).resolve().parent / 'src'}: {e}",
              file=sys.stderr)
        return 2
    dev = require_tpu()
    cache = enable_compile_cache()
    clock = CompileClock()
    say(phase="start", platform=dev.platform, kind=dev.device_kind,
        devices=len(jax.devices()), compile_cache=cache)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(args.seed, clock)
        else:
            one_chip(args.seed, dev, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say(phase="done", wall_s=time.perf_counter() - t0, **clock.read())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
