"""Chaos suite: the serving stack under the deterministic fault plane.

The PR 9 acceptance pins, exercised through seeded ``FaultPlan``
schedules over the real endpoint loop:

- **exactly-once** — every submitted request gets exactly one terminal
  response (``ok``/``rejected``/``timeout``/``error``), under every
  schedule;
- **no slot leaks** — ``_inflight`` returns to zero after every load,
  faulted or not;
- **the loop survives** — after arbitrary drain failures (including
  every drain failing) the same service instance serves the next load
  normally;
- **ok is ok** — every ``"ok"`` response is byte-identical to a
  fault-free serial ``QueryEngine.run`` of the same query;
- **zero-overhead disarmed** — with no plan armed, the fault plane adds
  zero registry mutations (all failure counters stay 0, no breaker
  instruments appear), mirroring the ``obs.enabled`` contract;
- **isolation** — a query poisoned at its ``unit.step`` seam is
  bisected out of its wave and answered ``"error"`` while its
  wave-mates are served untouched;
- **deadlines** — an expired budget resolves ``"timeout"`` at a unit
  boundary with the stats accumulated so far, counted in
  ``sched.deadline_expired``;
- **breaker** — repeated kernel faults open the per-op circuit breaker
  (oracle fallback, byte-identical), a half-open probe recovers it, and
  ``BREAKER.generation`` moves so compiled steps retrace.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import faults, obs
from repro.core import (
    EngineConfig,
    QueryEngine,
    QueryScheduler,
    results_as_numpy,
)
from repro.core.engine import plan_query
from repro.core.fragcache import FragmentCache, FragmentEntry
from repro.core.patterns import BGP, C, TriplePattern, V
from repro.endpoint import wire
from repro.endpoint.service import (
    EndpointRequest,
    EndpointService,
    ServiceConfig,
)
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.rdf import TripleStore


@pytest.fixture(autouse=True)
def _clean_fault_plane():
    yield
    faults.disarm()
    kops.BREAKER.reset()


def _tiny_store():
    s = np.array([0, 0, 1, 1, 2, 2, 3, 3])
    p = np.array([0, 1, 0, 1, 0, 1, 0, 1])
    o = np.array([3, 4, 3, 5, 3, 4, 4, 5])
    return TripleStore.build(s, p, o, n_terms=6, n_predicates=2)


def _two_star_bgp() -> BGP:
    return BGP((TriplePattern(V(0), C(0), V(1)),
                TriplePattern(V(0), C(1), V(2)),
                TriplePattern(V(1), C(0), V(3))), 4)


def _one_star_bgp() -> BGP:
    return BGP((TriplePattern(V(0), C(0), V(1)),), 2)


def _serial_rows(store, cfg, queries):
    eng = QueryEngine(store, cfg)
    out = []
    for q in queries:
        table, _ = eng.run(q)
        out.append(results_as_numpy(table))
    return out


def _fresh_service(store, **cfg_kw):
    cfg = EngineConfig(interface="endpoint")
    sched = QueryScheduler(store, cfg)
    cfg_kw.setdefault("drain_backoff_s", 0.0)
    return EndpointService(sched, ServiceConfig(**cfg_kw)), sched


def _assert_clean(svc):
    assert all(v == 0 for v in svc._inflight.values())
    assert svc._waiting == []


# --------------------------------------------------------------------------
# the fault plan itself
# --------------------------------------------------------------------------

def test_fault_plan_schedules_are_deterministic():
    """Same seed + specs -> the same calls fire, run after run."""
    def fires(seed):
        plan = faults.FaultPlan(seed, {
            "s": [faults.FaultSpec("raise", p=0.4),
                  faults.FaultSpec("raise", nth=(3, 7))],
        })
        hit = []
        for i in range(20):
            try:
                plan.hit("s", i=i)
            except faults.InjectedFault:
                hit.append(i)
        return hit, dict(plan.fired)

    assert fires(11) == fires(11)
    assert fires(11) != fires(12)  # a different seed is a different run


def test_fault_spec_when_filter_and_times_bound():
    plan = faults.FaultPlan(0, {
        "s": faults.FaultSpec("raise", when={"tag": "bad"}, times=2),
    })
    plan.hit("s", tag="good")  # filtered: never fires
    for _ in range(2):
        with pytest.raises(faults.InjectedFault):
            plan.hit("s", tag="bad")
    plan.hit("s", tag="bad")  # times exhausted
    assert plan.fired == {"s": 2}


def test_mangle_corrupts_payload_deterministically():
    plan_a = faults.FaultPlan(3, {"w": faults.FaultSpec("corrupt")})
    plan_b = faults.FaultPlan(3, {"w": faults.FaultSpec("corrupt")})
    data = bytes(range(256))
    out_a = plan_a.mangle("w", data)
    out_b = plan_b.mangle("w", data)
    assert out_a != data and out_a == out_b
    assert len(out_a) == len(data)


def test_injecting_context_restores_previous_plan():
    assert faults.plan is None
    with faults.injecting(faults.FaultPlan(0, {})):
        assert faults.plan is not None
        with faults.injecting(faults.FaultPlan(1, {})) as inner:
            assert faults.plan is inner
        assert faults.plan is not None and faults.plan.seed == 0
    assert faults.plan is None


# --------------------------------------------------------------------------
# chaos: the endpoint under seeded schedules
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_chaos_exactly_once_no_leak_ok_byte_identical(seed):
    """The headline acceptance pin, per seeded schedule: every request
    resolves exactly once, no admission slot leaks, every "ok" row block
    is byte-identical to the fault-free serial run, and the same service
    keeps serving after the plan is disarmed."""
    store = _tiny_store()
    cfg = EngineConfig(interface="endpoint")
    queries = [_two_star_bgp(), _one_star_bgp()]
    want = _serial_rows(store, cfg, queries)

    svc, sched = _fresh_service(store)
    reqs = [EndpointRequest(client=i % 4, query=queries[i % 2])
            for i in range(12)]
    plan = faults.FaultPlan(seed, {
        "drain": faults.FaultSpec("raise", p=0.25),
        "unit.step": faults.FaultSpec("raise", p=0.15),
        "cache.replay": faults.FaultSpec("raise", p=0.25),
    })
    with faults.injecting(plan):
        resps = svc.serve(reqs)

    assert len(resps) == len(reqs)  # exactly one terminal response each
    _assert_clean(svc)
    for r, req in zip(resps, reqs):
        assert r.status in ("ok", "error")
        if r.status == "ok":
            assert r.rows.tobytes() == want[reqs.index(req) % 2].tobytes()

    # disarmed again: the same instance serves the next load perfectly
    after = svc.serve([EndpointRequest(client=0, query=queries[0])])
    assert after[0].status == "ok"
    assert after[0].rows.tobytes() == want[0].tobytes()
    _assert_clean(svc)


def test_service_survives_every_drain_failing():
    """A hard drain poison (every call raises): the retry budget
    exhausts, every request resolves "error", nothing leaks, and the
    loop is alive for the next (clean) load."""
    store = _tiny_store()
    svc, sched = _fresh_service(store, drain_retries=3)
    reqs = [EndpointRequest(client=i, query=_two_star_bgp())
            for i in range(3)]
    with faults.injecting(
            faults.FaultPlan(0, {"drain": faults.FaultSpec("raise")})):
        resps = svc.serve(reqs)
    assert [r.status for r in resps] == ["error"] * 3
    _assert_clean(svc)
    snap = sched.snapshot()
    assert snap["endpoint.drain_faults"] > 0
    assert snap["endpoint.errors"] == 3

    ok = svc.serve([EndpointRequest(client=0, query=_two_star_bgp())])
    assert ok[0].status == "ok"
    _assert_clean(svc)


def test_poisoned_query_is_bisected_out_and_wave_mates_served():
    """The isolation pin (and the PR 8 in-flight-leak regression): one
    query whose waves always fault is answered "error"; the other
    requests in the same wave are served byte-identically; the service
    serves the next wave afterward."""
    store = _tiny_store()
    cfg = EngineConfig(interface="endpoint")
    good, poison = _one_star_bgp(), _two_star_bgp()
    want_good = _serial_rows(store, cfg, [good])[0]
    poison_sig = plan_query(store, poison, cfg).signature

    svc, sched = _fresh_service(store)
    reqs = [EndpointRequest(client=c, query=good) for c in range(4)] \
        + [EndpointRequest(client=4, query=poison)]
    plan = faults.FaultPlan(0, {
        "unit.step": faults.FaultSpec("raise", when={"sig": poison_sig}),
        "cache.replay": faults.FaultSpec("raise", when={"sig": poison_sig}),
    })
    with faults.injecting(plan):
        resps = svc.serve(reqs)

    assert [r.status for r in resps[:4]] == ["ok"] * 4
    for r in resps[:4]:
        assert r.rows.tobytes() == want_good.tobytes()
    assert resps[4].status == "error"
    _assert_clean(svc)
    snap = sched.snapshot()
    assert snap["endpoint.drain_bisects"] >= 1
    assert snap["endpoint.drain_retries"] >= 1

    # regression (PR 8): the poisoned wave did not leak slots or kill
    # the loop — the next wave serves, including for the poison's client
    after = svc.serve([EndpointRequest(client=4, query=good)])
    assert after[0].status == "ok"
    _assert_clean(svc)


def test_transient_drain_fault_recovers_by_retry():
    """A fault that fires once (nth=1) costs one retry, not a response:
    everything still resolves "ok"."""
    store = _tiny_store()
    cfg = EngineConfig(interface="endpoint")
    want = _serial_rows(store, cfg, [_two_star_bgp()])[0]
    svc, sched = _fresh_service(store)
    with faults.injecting(faults.FaultPlan(
            0, {"drain": faults.FaultSpec("raise", nth=1)})):
        resps = svc.serve([EndpointRequest(client=c, query=_two_star_bgp())
                           for c in range(3)])
    assert [r.status for r in resps] == ["ok"] * 3
    for r in resps:
        assert r.rows.tobytes() == want.tobytes()
    snap = sched.snapshot()
    assert snap["endpoint.drain_faults"] == 1
    assert snap["endpoint.drain_retries"] == 1
    _assert_clean(svc)


def test_parse_seam_resolves_error_not_crash():
    store = _tiny_store()
    svc, sched = _fresh_service(store)
    text = "SELECT * WHERE { ?a <0> ?b }"
    with faults.injecting(faults.FaultPlan(
            0, {"parse": faults.FaultSpec("raise", nth=1)})):
        bad, ok = svc.serve([EndpointRequest(client=0, sparql=text),
                             EndpointRequest(client=1, sparql=text)])
    assert bad.status == "error" and "injected" in bad.error
    assert ok.status == "ok"
    assert sched.snapshot()["endpoint.parse_errors"] == 1
    _assert_clean(svc)


def test_disarmed_fault_plane_adds_zero_registry_mutations():
    """The ``obs.enabled`` twin contract: with no plan armed, serving a
    load moves none of the failure instruments and surfaces no breaker
    keys — the plane is invisible."""
    assert faults.plan is None
    store = _tiny_store()
    svc, sched = _fresh_service(store)
    resps = svc.serve([EndpointRequest(client=c, query=_two_star_bgp())
                       for c in range(3)])
    assert [r.status for r in resps] == ["ok"] * 3
    snap = sched.snapshot()
    for field in ("drain_faults", "drain_retries", "drain_bisects",
                  "timeouts", "errors", "shed"):
        assert snap.get(f"endpoint.{field}", 0) == 0
    assert snap.get("sched.deadline_expired", 0) == 0
    assert not any(k.startswith("kernels.breaker") for k in snap)
    assert kops.BREAKER.snapshot() == {}


# --------------------------------------------------------------------------
# deadlines
# --------------------------------------------------------------------------

def test_scheduler_expires_at_unit_boundary_with_partial_stats():
    store = _tiny_store()
    sched = QueryScheduler(store, EngineConfig(interface="endpoint"))
    rid = sched.submit(_two_star_bgp(), deadline=time.perf_counter() - 1.0)
    results = sched.drain()
    table, stats = results[rid]
    assert table is None  # the timeout marker
    assert stats.n_results == 0
    assert sched.metrics.deadline_expired == 1


def test_no_deadline_duplicate_shields_collapsed_job():
    """Request collapsing: a no-deadline submitter is owed a full
    result, so an expired duplicate cannot expire the shared job."""
    store = _tiny_store()
    sched = QueryScheduler(store, EngineConfig(interface="endpoint"))
    rid_dead = sched.submit(_two_star_bgp(),
                            deadline=time.perf_counter() - 1.0)
    rid_live = sched.submit(_two_star_bgp())  # collapses onto the same job
    results = sched.drain()
    assert results[rid_dead][0] is not None
    assert results[rid_live][0] is not None
    assert sched.metrics.deadline_expired == 0


def test_endpoint_deadline_resolves_timeout_with_stats():
    store = _tiny_store()
    cfg = EngineConfig(interface="endpoint")
    want = _serial_rows(store, cfg, [_two_star_bgp()])[0]
    svc, sched = _fresh_service(store)
    expired, fine = svc.serve([
        EndpointRequest(client=0, query=_two_star_bgp(), deadline_s=0.0),
        EndpointRequest(client=1, query=_one_star_bgp(), deadline_s=60.0),
    ])
    assert expired.status == "timeout"
    assert expired.rows is None and expired.stats is not None
    assert fine.status == "ok"
    snap = sched.snapshot()
    assert snap["endpoint.timeouts"] == 1
    assert snap["sched.deadline_expired"] == 1
    _assert_clean(svc)

    # a generous deadline serves normally, byte-identical
    ok = svc.serve([EndpointRequest(client=0, query=_two_star_bgp(),
                                    deadline_s=300.0)])
    assert ok[0].status == "ok"
    assert ok[0].rows.tobytes() == want.tobytes()


# --------------------------------------------------------------------------
# overload shedding
# --------------------------------------------------------------------------

def test_overload_sheds_with_retry_after_hint():
    store = _tiny_store()
    svc, sched = _fresh_service(store, max_queue=2,
                                max_inflight_per_client=64)
    reqs = [EndpointRequest(client=c, query=_one_star_bgp())
            for c in range(6)]
    resps = svc.serve(reqs)
    statuses = [r.status for r in resps]
    assert statuses.count("rejected") >= 1  # the queue bound shed some
    assert statuses.count("ok") >= 2
    for r in resps:
        if r.status == "rejected":
            assert r.retry_after_s is not None and r.retry_after_s > 0
            assert r.error == "service overloaded"
    snap = sched.snapshot()
    assert snap["endpoint.shed"] == statuses.count("rejected")
    _assert_clean(svc)


# --------------------------------------------------------------------------
# the kernel circuit breaker
# --------------------------------------------------------------------------

def test_kernel_breaker_opens_serves_oracle_and_recovers():
    """Per-op breaker lifecycle under the ``kernel`` seam: faults below
    the threshold fall back per-call; the threshold opens the breaker
    (oracle-only); ``cooldown`` blocked calls arm a half-open probe; a
    clean probe closes it.  Every output along the way is byte-identical
    to the oracle, and ``generation`` moves on each transition."""
    br = kops.BREAKER
    br.reset()
    old_force = kops.FORCE
    kops.FORCE = "pallas"
    try:
        keys = jnp.asarray(np.sort(np.random.default_rng(0)
                                   .integers(0, 99, size=64)), jnp.int32)
        qs = jnp.asarray([0, 7, 50, 98], jnp.int32)
        want = tuple(np.asarray(x) for x in ref.sorted_probe_ref(keys, qs))

        def check():
            got = kops.sorted_probe(keys, qs)
            assert np.array_equal(np.asarray(got[0]), want[0])
            assert np.array_equal(np.asarray(got[1]), want[1])

        gen0 = br.generation
        plan = faults.FaultPlan(0, {
            "kernel": faults.FaultSpec("raise",
                                       when={"prim": "sorted_probe"},
                                       times=br.threshold),
        })
        with faults.injecting(plan):
            for _ in range(br.threshold):  # each faults -> oracle fallback
                check()
        assert br.state("sorted_probe") == br.OPEN
        assert br.generation > gen0
        assert br.snapshot() == {"sorted_probe": br.OPEN}

        for _ in range(br.cooldown):  # blocked calls, oracle-served
            check()
        assert br.state("sorted_probe") == br.HALF_OPEN
        check()  # the probe: Pallas path clean -> closed
        assert br.state("sorted_probe") == br.CLOSED
        assert br.snapshot() == {}
    finally:
        kops.FORCE = old_force
        br.reset()


def test_kernel_fault_is_logged_once_per_primitive(caplog):
    """A Pallas fault is never silent: the first one of a primitive is
    logged with its exception before the oracle serves the call; repeats
    of the same primitive do not flood the log."""
    br = kops.BREAKER
    br.reset()
    old_force = kops.FORCE
    kops.FORCE = "pallas"
    kops._LOGGED_FAULTS.discard("sorted_probe")
    try:
        keys = jnp.asarray(np.arange(0, 128, 2), jnp.int32)
        qs = jnp.asarray([0, 7, 50, 98], jnp.int32)
        plan = faults.FaultPlan(0, {
            "kernel": faults.FaultSpec("raise",
                                       when={"prim": "sorted_probe"},
                                       times=2),
        })
        with caplog.at_level("WARNING", logger=kops.__name__), \
                faults.injecting(plan):
            for _ in range(2):
                got = kops.sorted_probe(keys, qs)
        assert np.array_equal(np.asarray(got[0]),
                              np.asarray(ref.sorted_probe_ref(keys, qs)[0]))
        logged = [r for r in caplog.records if "sorted_probe" in r.message]
        assert len(logged) == 1 and logged[0].exc_info is not None
    finally:
        kops.FORCE = old_force
        br.reset()


def test_kernel_breaker_failed_probe_reopens():
    br = kops.BREAKER
    br.reset()
    old_force = kops.FORCE
    kops.FORCE = "pallas"
    try:
        keys = jnp.asarray([1, 2, 3, 4], jnp.int32)
        qs = jnp.asarray([2, 5], jnp.int32)
        plan = faults.FaultPlan(0, {"kernel": faults.FaultSpec(
            "raise", when={"prim": "sorted_probe"})})  # hard poison
        with faults.injecting(plan):
            for _ in range(br.threshold):
                kops.sorted_probe(keys, qs)
            assert br.state("sorted_probe") == br.OPEN
            for _ in range(br.cooldown):
                kops.sorted_probe(keys, qs)
            assert br.state("sorted_probe") == br.HALF_OPEN
            kops.sorted_probe(keys, qs)  # probe faults too
            assert br.state("sorted_probe") == br.OPEN
    finally:
        kops.FORCE = old_force
        br.reset()


def test_breaker_transition_forces_step_retrace():
    """The generation key: a breaker transition changes the stepper's
    jit-cache keys, so compiled steps cannot keep serving a stale
    dispatch decision."""
    from repro.core import stepper

    store = _tiny_store()
    cfg = EngineConfig(interface="endpoint")
    plan = plan_query(store, _one_star_bgp(), cfg)
    up = plan.units[0]
    s1 = stepper.unit_step(up, store.radix)
    assert stepper.unit_step(up, store.radix) is s1  # cached
    kops.BREAKER._transition("sorted_probe", kops.BREAKER.OPEN)
    try:
        assert stepper.unit_step(up, store.radix) is not s1  # retraced
    finally:
        kops.BREAKER.reset()


def test_chaos_kernel_faults_end_to_end_byte_identical():
    """Kernel-seam chaos through the full endpoint: seeded faults inside
    the Pallas wrappers degrade to the oracle (possibly opening
    breakers) but every response stays "ok" and byte-identical."""
    store = _tiny_store()
    cfg = EngineConfig(interface="endpoint")
    queries = [_two_star_bgp(), _one_star_bgp()]
    want = _serial_rows(store, cfg, queries)
    old_force = kops.FORCE
    kops.FORCE = "pallas"
    kops.BREAKER.reset()
    try:
        svc, sched = _fresh_service(store)
        with faults.injecting(faults.FaultPlan(
                9, {"kernel": faults.FaultSpec("raise", p=0.3)})):
            resps = svc.serve([EndpointRequest(client=i % 3,
                                               query=queries[i % 2])
                               for i in range(8)])
        assert [r.status for r in resps] == ["ok"] * 8
        for i, r in enumerate(resps):
            assert r.rows.tobytes() == want[i % 2].tobytes()
        _assert_clean(svc)
    finally:
        kops.FORCE = old_force
        kops.BREAKER.reset()


# --------------------------------------------------------------------------
# wire corruption through the fault seam
# --------------------------------------------------------------------------

def _warm_cache(n=6):
    cache = FragmentCache(capacity=16)
    rng = np.random.default_rng(0)
    for i in range(n):
        e = FragmentEntry(rng.integers(0, 50, size=(3,)).astype(np.int32),
                          rng.integers(0, 50, size=(3, 2)).astype(np.int32),
                          False, i, 0, i + 1)
        cache.put(("k", i), e, epoch=0)
    return cache


def test_wire_loads_seam_corruption_never_adopts_bad_records():
    """Armed byte corruption on the ``wire.loads`` seam: either the
    framing is hit (whole blob rejected, nothing adopted) or the CRC
    quarantine skips exactly the damaged records — every record that IS
    adopted is byte-identical to the donor's."""
    donor = _warm_cache()
    blob = wire.dumps_cache(donor, 0)
    donor_entries = dict(donor.export_state()[0])
    quarantined = rejected = 0
    for seed in range(8):
        fresh = FragmentCache(capacity=16)
        with faults.injecting(faults.FaultPlan(seed, {
                "wire.loads": faults.FaultSpec("corrupt", bit_flips=6)})):
            try:
                wire.restore_cache(blob, fresh, 0)
            except wire.WireError:
                rejected += 1
                assert len(fresh) == 0  # whole-blob reject adopts nothing
                continue
        if fresh.stats.wire_corrupt:
            quarantined += 1
        for key in donor_entries:
            got = fresh.get(key, epoch=0)
            if got is not None:
                want = donor_entries[key]
                assert got.src_row.tobytes() == want.src_row.tobytes()
                assert got.written.tobytes() == want.written.tobytes()
    # across 8 seeded corruptions at least one exercised each path
    assert quarantined + rejected > 0
