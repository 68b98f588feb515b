"""Observability layer: registry/tracer mechanics + metric invariants.

Three layers of contract:

- **unit** — the dependency-free registry (geometric histograms, quantile
  extraction, snapshot diffs, ``RegistryView`` facades).
- **zero overhead off** — with ``obs`` disabled (the default) the serving
  stack never touches the global registry, never imports the tracer
  module, and produces byte-identical results to a traced run.
- **metric invariants** — the counters and spans agree with each other
  and with what ``benchlib`` charges: all-hit waves pull zero Omega
  blocks AND emit one ``cache.replay_device`` span per replayed unit;
  overflow-resume emits exactly one ``overflow.resume`` span per retried
  unit; a sharded serve's snapshot-diffed ``sched.gather_bytes`` is
  exactly the payload the throughput model charges against the pod
  interconnect.
"""

import json
import os
import subprocess
import sys
from dataclasses import replace

import numpy as np
import pytest

from repro import obs
from repro.core import (
    EngineConfig,
    QueryEngine,
    QueryScheduler,
    SchedulerConfig,
    interleave_clients,
    results_as_numpy,
)
from repro.obs.registry import MetricsRegistry, RegistryView, Snapshot
from repro.rdf import generate_query_load
from repro.rdf.queries import QueryLoadConfig


# --------------------------------------------------------------------------
# registry unit tests
# --------------------------------------------------------------------------

def test_registry_scalars_and_views():
    reg = MetricsRegistry()
    reg.inc("a.x")
    reg.inc("a.x", 4)
    reg.set_value("a.y", 2.5)
    assert reg.value("a.x") == 5
    assert reg.value("a.y") == 2.5
    assert reg.value("missing") == 0

    class V(RegistryView):
        _PREFIX = "a"
        _FIELDS = ("x", "y")

    v = V(reg)
    assert v.x == 5
    v.x += 1  # property get + set — the old `stats.x += 1` call sites
    assert reg.value("a.x") == 6
    assert v.as_dict() == {"x": 6, "y": 2.5}
    v.reset()
    assert v.x == 0 and reg.value("a.x") == 0
    # a view without a registry owns a private one
    w = V()
    w.x += 3
    assert w.x == 3 and reg.value("a.x") == 0
    assert w != v


def test_histogram_percentiles_within_bucket_error():
    reg = MetricsRegistry()
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-3.0, sigma=1.0, size=4000)
    for x in vals:
        reg.observe("lat", float(x))
    for q in (0.50, 0.95, 0.99):
        got = reg.percentile("lat", q)
        true = float(np.quantile(vals, q))
        # geometric buckets are ~9% wide (base 2**(1/8)): the reported
        # upper edge sits within one bucket of the true quantile
        assert true * 0.9 <= got <= true * 1.1, (q, got, true)
    s = reg.snapshot()["lat"]
    assert s["count"] == 4000
    assert s["sum"] == pytest.approx(vals.sum())
    assert s["min"] <= s["p50"] <= s["p95"] <= s["p99"] <= s["max"]


def test_histogram_nonpositive_observations():
    reg = MetricsRegistry()
    reg.observe("h", 0.0)
    reg.observe("h", -1.0)
    reg.observe("h", 1.0)
    s = reg.snapshot()["h"]
    assert s["count"] == 3
    assert s["p50"] == 0.0  # two of three observations are <= 0
    assert reg.percentile("h", 0.99) >= 1.0 * (2 ** -0.125)


def test_snapshot_diff_scalars_and_histograms():
    reg = MetricsRegistry()
    reg.inc("n", 10)
    for v in (1.0, 2.0, 4.0):
        reg.observe("h", v)
    a = reg.snapshot()
    reg.inc("n", 5)
    reg.inc("new", 7)
    for v in (8.0, 16.0):
        reg.observe("h", v)
    b = reg.snapshot()
    d = b - a
    assert isinstance(d, Snapshot)
    assert d.scalar("n") == 5
    assert d.scalar("new") == 7  # absent from the baseline -> full value
    assert d["h"]["count"] == 2
    assert d["h"]["sum"] == pytest.approx(24.0)
    # interval quantiles come from the bucket diff, not the cumulative one
    assert d["h"]["p50"] >= 4.0
    assert d.scalar("h") == 0  # scalar() on a histogram entry -> default


def test_histogram_single_observation_true_min_max():
    """A single observation's summary must report that exact value as
    both min and max (the old code reported the geometric bucket's upper
    edge, so ``min`` exceeded the only observed value and ``mean`` could
    sit below ``min``)."""
    reg = MetricsRegistry()
    reg.observe("h", 3.0)
    s = reg.snapshot()["h"]
    assert s["min"] == 3.0 and s["max"] == 3.0
    assert s["mean"] == pytest.approx(3.0)
    assert s["min"] <= s["mean"] <= s["max"]
    assert s["min"] <= s["p50"] <= s["p99"] <= s["max"]
    # more observations keep the true extrema exact
    reg.observe("h", 0.7)
    reg.observe("h", 11.0)
    s = reg.snapshot()["h"]
    assert s["min"] == 0.7 and s["max"] == 11.0
    assert s["min"] <= s["p50"] <= s["p95"] <= s["p99"] <= s["max"]


def test_snapshot_diff_min_max_bound_interval_observations():
    """Interval diffs cannot recover true extrema from bucket counts, but
    the reported min/max must still bound every interval observation
    (lower edge of the lowest occupied bucket / upper edge of the
    highest), and an empty-baseline diff keeps the endpoint's exact
    extrema."""
    reg = MetricsRegistry()
    for v in (1.0, 2.0):
        reg.observe("h", v)
    a = reg.snapshot()
    for v in (8.0, 16.0):
        reg.observe("h", v)
    d = reg.snapshot() - a
    assert d["h"]["count"] == 2
    assert d["h"]["min"] <= 8.0 and d["h"]["max"] >= 16.0
    assert d["h"]["min"] <= d["h"]["mean"] <= d["h"]["max"]
    assert d["h"]["min"] <= d["h"]["p50"] <= d["h"]["p99"] <= d["h"]["max"]
    # empty baseline: the interval IS the endpoint -> exact extrema
    b = reg.snapshot() - Snapshot()
    assert b["h"]["min"] == 1.0 and b["h"]["max"] == 16.0


def test_registry_reset_by_prefix():
    reg = MetricsRegistry()
    reg.inc("a.x")
    reg.inc("b.y")
    reg.observe("a.h", 1.0)
    reg.reset("a.")
    snap = reg.snapshot()
    assert "a.x" not in snap and "a.h" not in snap
    assert snap["b.y"] == 1
    reg.reset()
    assert len(reg) == 0


# --------------------------------------------------------------------------
# zero overhead when disabled
# --------------------------------------------------------------------------

def test_disabled_by_default_and_lazy_tracer_import():
    """Importing the serving stack must not import the tracer module, and
    obs must default to off (checked in a clean interpreter so earlier
    tests cannot have warmed sys.modules)."""
    env = dict(os.environ, PYTHONPATH="src")
    code = ("import repro, repro.core.scheduler, repro.core.engine, "
            "repro.kernels.ops, sys\n"
            "from repro import obs\n"
            "assert not obs.enabled and obs.tracer is None\n"
            "assert 'repro.obs.trace' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=os.path.dirname(os.path.dirname(__file__)))


def test_disabled_zero_registry_mutation_and_byte_identity(watdiv_small):
    """The pinned tentpole invariant: with obs off (default), serving
    mutates NO global-registry instrument, and enabling tracing (spans
    and all) changes no result byte and no gross stat."""
    g, store = watdiv_small
    qs = generate_query_load(g, store, "union", QueryLoadConfig(n_queries=3))
    cfg = EngineConfig(interface="spf", cap=2048)
    stream = interleave_clients(list(qs), 3)

    obs.registry.reset()
    assert not obs.enabled
    sched = QueryScheduler(store, cfg, SchedulerConfig(lanes=8))
    plain = sched.serve(stream)
    assert len(obs.registry) == 0, dict(obs.registry.snapshot())

    sched_t = QueryScheduler(store, cfg, SchedulerConfig(lanes=8))
    with obs.tracing() as tracer:
        traced = sched_t.serve(stream)
    assert not obs.enabled and obs.tracer is None  # context restored
    assert tracer.events, "tracing recorded nothing"
    for (a, sa), (b, sb) in zip(plain, traced):
        assert np.array_equal(results_as_numpy(a), results_as_numpy(b))
        assert tuple(int(x) for x in sa)[:6] == tuple(int(x) for x in sb)[:6]
    obs.registry.reset()


# --------------------------------------------------------------------------
# metric invariants
# --------------------------------------------------------------------------

def test_all_hit_wave_replay_spans_and_zero_pulls(watdiv_small):
    """All-hit waves: zero host Omega-block pulls AND one
    ``cache.replay_device`` span per replayed unit (= the
    ``steps_skipped`` delta — every skipped step is a device replay)."""
    g, store = watdiv_small
    qs = generate_query_load(g, store, "union", QueryLoadConfig(n_queries=3))
    cfg = EngineConfig(interface="spf", cap=2048)
    # cap_hints off keeps cache keys identical across passes: pass 2 is
    # all-hit by construction
    sched = QueryScheduler(store, cfg,
                           SchedulerConfig(lanes=8, cap_hints=False))
    sched.run_queries(qs)
    base = sched.snapshot()
    with obs.tracing() as tracer:
        _, stats = sched.run_queries(qs)
    diff = sched.snapshot() - base
    assert all(int(s.cache_misses) == 0 for s in stats)
    assert diff.scalar("sched.host_block_pulls") == 0
    assert diff.scalar("sched.steps") == 0
    n_replayed = diff.scalar("sched.steps_skipped")
    assert n_replayed > 0
    assert tracer.count("cache.replay_device", "X") == n_replayed
    # every replay span sits inside a unit span on the replay path
    units = [e for e in tracer.named("unit") if e["ph"] == "X"]
    assert sum(1 for e in units if e["args"].get("path") == "replay") \
        == n_replayed
    obs.registry.reset()


def test_submit_walls_reaped_across_obs_toggle(watdiv_small):
    """``_t_submit`` entries recorded while obs was on must be reaped
    even when the drain runs with obs off — a submit-traced /
    drain-untraced toggle used to leak them forever."""
    g, store = watdiv_small
    qs = generate_query_load(g, store, "1-star", QueryLoadConfig(n_queries=2))
    cfg = EngineConfig(interface="spf", cap=2048)
    sched = QueryScheduler(store, cfg, SchedulerConfig(lanes=8))
    obs.enable(trace=False)
    try:
        for q in qs:
            sched.submit(q)
        assert len(sched._t_submit) == len(qs)  # walls were recorded
    finally:
        obs.disable()
    sched.drain()  # obs now off: the old code skipped the reap entirely
    assert sched._t_submit == {}
    # and the normal obs-on path still reaps and records latencies
    with obs.tracing(trace=False):
        sched.run_queries(qs)
        assert sched._t_submit == {}
    assert sched.snapshot()["sched.query_latency_s"]["count"] == len(qs)
    obs.registry.reset()


def test_overflow_resume_one_span_per_retry(watdiv_small):
    """Exactly one ``overflow.resume`` span per retried unit — the span
    count is the ``retries`` counter, on the nose."""
    g, store = watdiv_small
    qs = generate_query_load(g, store, "2-stars",
                             QueryLoadConfig(n_queries=3))
    # tiny starting capacity + no planner: the 4x retry ladder must fire
    cfg = EngineConfig(interface="spf", cap=4, capacity_planner=False)
    sched = QueryScheduler(store, cfg, SchedulerConfig(lanes=8))
    with obs.tracing() as tracer:
        sched.run_queries(qs)
    assert sched.metrics.retries > 0, "fixture must actually overflow"
    assert tracer.count("overflow.resume", "X") == sched.metrics.retries
    obs.registry.reset()


def test_engine_query_spans_and_latency(watdiv_small):
    """The single-query path wraps each ``run`` in an ``engine.query``
    span and lands its wall latency in the global registry's
    ``engine.query_latency_s`` histogram — only under obs."""
    g, store = watdiv_small
    qs = generate_query_load(g, store, "2-stars",
                             QueryLoadConfig(n_queries=3))
    cfg = EngineConfig(interface="spf", cap=4)
    eng = QueryEngine(store, cfg)
    with obs.tracing() as tracer:
        for q in qs:
            eng.run(q)
    assert tracer.count("engine.query", "X") == len(qs)
    assert obs.registry.snapshot()["engine.query_latency_s"]["count"] \
        == len(qs)
    obs.registry.reset()


def test_sharded_gather_bytes_matches_benchlib_charge(watdiv_small):
    """The ``sched.gather_bytes`` snapshot diff is exactly the payload
    ``benchlib.scheduled_load_throughput`` charges against the pod
    interconnect: solving two throughput runs that differ only in
    ``pod_bw_bytes_s`` for the charged byte count recovers the
    registry's number.  (On one visible device this runs the 1-shard
    sharded lowering; the CI dist job re-runs it at real shard counts —
    keep ``shard`` in the name.)"""
    import jax

    from repro.benchlib import CostModel, scheduled_load_throughput

    g, store = watdiv_small
    qs = generate_query_load(g, store, "union", QueryLoadConfig(n_queries=2))
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev, 1), ("data", "model"))
    cfg = EngineConfig(interface="spf", cap=2048)
    # cache and hints off: consecutive serves of the same stream do
    # identical work, so both throughput runs charge the same bytes
    sched = QueryScheduler(
        store, cfg,
        SchedulerConfig(lanes=8, use_cache=False, cap_hints=False,
                        collapse_duplicates=False),
        mesh=mesh, data_axis="data")
    n_clients = 2
    cm1 = CostModel()
    cm2 = replace(cm1, pod_bw_bytes_s=cm1.pod_bw_bytes_s / 1000.0)

    # steady state first: the planner's shard-peak hints warm on the
    # first serve and would shrink pass 2's merge trims (fewer bytes)
    from repro.core.scheduler import interleave_clients
    sched.serve(interleave_clients(list(qs), n_clients))

    base = sched.snapshot()
    t1, _, _ = scheduled_load_throughput(store, qs, "spf", n_clients,
                                         cm=cm1, scheduler=sched)
    g_measured = (sched.snapshot() - base).scalar("sched.gather_bytes")
    assert g_measured > 0
    assert g_measured == sched.metrics.gather_bytes - \
        base.scalar("sched.gather_bytes")
    t2, _, _ = scheduled_load_throughput(store, qs, "spf", n_clients,
                                         cm=cm2, scheduler=sched)
    n_req = len(qs) * n_clients
    total1 = n_req * n_clients * 60.0 / t1
    total2 = n_req * n_clients * 60.0 / t2
    g_charged = (total2 - total1) / (1.0 / cm2.pod_bw_bytes_s
                                     - 1.0 / cm1.pod_bw_bytes_s)
    assert g_charged == pytest.approx(g_measured, rel=1e-6)


# --------------------------------------------------------------------------
# trace export (the Perfetto acceptance gate)
# --------------------------------------------------------------------------

def test_traced_64_client_union_load_perfetto(watdiv_small, tmp_path):
    """A traced 64-client union load exports a Chrome-trace JSON with the
    full query -> wave -> unit -> kernel hierarchy: per-query async
    begin/end pairs, wave/unit/unit.step complete events with positional
    nesting, and trace-time ``kernel.*`` dispatch instants."""
    g, store = watdiv_small
    qs = generate_query_load(g, store, "union", QueryLoadConfig(n_queries=2))
    # a cap no other test uses: the unit steps must re-trace inside the
    # traced region so kernel dispatch notes actually fire
    cfg = EngineConfig(interface="spf", cap=1024)
    sched = QueryScheduler(store, cfg, SchedulerConfig(lanes=8))
    stream = interleave_clients(list(qs), 64)
    with obs.tracing() as tracer:
        served = sched.serve(stream)
    assert len(served) == len(stream)

    path = tmp_path / "TRACE_test.json"
    tracer.export_chrome(str(path))
    doc = json.loads(path.read_text())
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    events = doc["traceEvents"]
    by_ph = {}
    for e in events:
        by_ph.setdefault(e["ph"], []).append(e)

    # async per-query lifetimes: one b/e pair per request
    q_b = [e for e in by_ph.get("b", []) if e["name"] == "query"]
    q_e = [e for e in by_ph.get("e", []) if e["name"] == "query"]
    assert len(q_b) == len(q_e) == len(stream)
    assert {e["id"] for e in q_b} == {e["id"] for e in q_e}

    # sync hierarchy: drain > wave > unit > unit.step, positionally nested
    x = {e["name"]: e for e in by_ph["X"]}
    for name in ("sched.drain", "wave", "unit", "unit.step"):
        assert name in x, name

    def spans(name):
        return [e for e in by_ph["X"] if e["name"] == name]

    def contains(outer, inner):
        return (outer["ts"] <= inner["ts"]
                and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
                + 1e-3)

    drain = spans("sched.drain")
    assert all(any(contains(d, w) for d in drain) for w in spans("wave"))
    assert all(any(contains(w, u) for w in spans("wave"))
               for u in spans("unit"))
    assert all(any(contains(u, s) for u in spans("unit"))
               for s in spans("unit.step"))

    # kernel dispatch instants recorded at trace time
    kernel_instants = [e for e in by_ph.get("i", [])
                       if e["name"].startswith("kernel.")]
    assert kernel_instants, "no kernel dispatch instants in the trace"
    disp = {k: v for k, v in obs.registry.snapshot().items()
            if k.startswith("kernels.dispatch.")}
    assert sum(disp.values()) >= len(kernel_instants) > 0
    obs.registry.reset()


# --------------------------------------------------------------------------
# program spans: one API, two sinks, no fences
# --------------------------------------------------------------------------

def test_span_is_a_shared_noop_when_disabled():
    """Off, ``obs.span`` hands out one shared object that records
    nothing: no registry instrument, no tracer, whatever its args."""
    obs.registry.reset()
    assert not obs.enabled
    a = obs.span("wave")
    b = obs.span("unit.pull", None, k=3)
    assert a is b
    with a as sp:
        sp.note(hits=1)
    assert len(obs.registry) == 0


def _host_events(tdir):
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
    return out


def test_profiler_host_plane_carries_program_spans(watdiv_small, tmp_path):
    """Registry-only mode (``trace=False``, as a traced benchmark window
    runs) puts the scheduler's spans in the profiler's host plane under
    exactly their names, each inside a ``wave`` event."""
    import jax

    g, store = watdiv_small
    qs = generate_query_load(g, store, "union", QueryLoadConfig(n_queries=2))
    cfg = EngineConfig(interface="spf", cap=2048)
    stream = interleave_clients(list(qs), 4)
    QueryScheduler(store, cfg, SchedulerConfig(lanes=8)).serve(stream)
    sched = QueryScheduler(store, cfg, SchedulerConfig(lanes=8))  # cold
    with obs.tracing(trace=False) as tracer:
        assert tracer is None
        with jax.profiler.trace(str(tmp_path)):
            sched.serve(stream)
    events = _host_events(tmp_path)
    waves = [(s, e) for n, s, e in events if n == "wave"]
    assert len(waves) == sched.metrics.waves
    for name in ("unit.wait", "unit.pull", "cache.insert", "wave.finalize",
                 "cache.probe"):
        spans = [(s, e) for n, s, e in events if n == name]
        assert spans, name
        assert all(any(ws <= s and e <= we for ws, we in waves)
                   for s, e in spans), name
    obs.registry.reset()


def test_host_syncs_per_unit_step(watdiv_small):
    """``sched.host_syncs`` counts every blocking call: per dispatched
    step the digest and the step's one transfer (its stats and, under the
    byte budget, the whole wave's rows the misses are recorded from), one
    per overflow checkpoint, one digest per replayed unit, and finalize's
    two per wave.  Every dispatched step had a miss and pulled its wave.
    An all-hit pass adds no transfers of a step: only its digests and
    finalizes."""
    g, store = watdiv_small
    qs = generate_query_load(g, store, "union", QueryLoadConfig(n_queries=3))
    cfg = EngineConfig(interface="spf", cap=2048)
    sched = QueryScheduler(store, cfg,
                           SchedulerConfig(lanes=8, cap_hints=False))
    with obs.tracing() as tracer:
        sched.run_queries(qs)
    m = sched.metrics
    inserted = tracer.count("cache.insert", "X")
    assert m.steps > 0 and inserted > 0
    assert m.host_block_pulls == inserted + m.retries
    assert m.wave_pulls == m.steps
    assert m.host_syncs == (2 * m.steps + m.retries
                            + m.steps_skipped + 2 * m.waves)

    base = sched.snapshot()
    _, stats = sched.run_queries(qs)  # obs off: counted all the same
    d = sched.snapshot() - base
    assert all(int(s.cache_misses) == 0 for s in stats)
    assert d.scalar("sched.steps") == 0
    assert d.scalar("sched.host_block_pulls") == 0
    assert d.scalar("sched.host_syncs") == \
        d.scalar("sched.steps_skipped") + 2 * d.scalar("sched.waves")
    obs.registry.reset()


def _span_sums(tracer) -> dict:
    sums: dict = {}
    for e in tracer.events:
        if e["ph"] == "X":
            sums[e["name"]] = sums.get(e["name"], 0.0) + e["dur"] * 1e-6
    return sums


def _span_s(snap, prefix: str) -> dict:
    head = f"{prefix}.span_s."
    return {k[len(head):]: v for k, v in snap.items() if k.startswith(head)}


def test_span_seconds_equal_tracer_events(watdiv_small):
    """With a tracer attached, each ``sched.span_s.<name>`` is exactly
    the summed duration of the in-memory ``<name>`` events; waves and
    the queries they answer share the wave's number.  Off, no
    ``span_s`` instrument exists anywhere."""
    g, store = watdiv_small
    qs = generate_query_load(g, store, "2-stars",
                             QueryLoadConfig(n_queries=3))
    cfg = EngineConfig(interface="spf", cap=2048)
    stream = interleave_clients(list(qs), 3)
    sched = QueryScheduler(store, cfg, SchedulerConfig(lanes=8))
    with obs.tracing() as tracer:
        sched.serve(stream)
    sums = _span_sums(tracer)
    got = _span_s(sched.snapshot(), "sched")
    assert set(got) == set(sums)
    for name in ("sched.drain", "sched.plan", "wave", "unit", "cache.probe",
                 "wave.lower", "unit.step", "unit.wait", "unit.pull",
                 "cache.insert", "unit.account", "wave.finalize"):
        assert got[name] == pytest.approx(sums[name], rel=1e-9), name
    waves = {e["args"]["wave"] for e in tracer.named("wave")}
    assert waves == set(range(1, sched.metrics.waves + 1))
    ends = [e for e in tracer.named("query") if e["ph"] == "e"]
    assert len(ends) == len(stream)
    assert {e["args"]["wave"] for e in ends} <= waves

    obs.registry.reset()
    off = QueryScheduler(store, cfg, SchedulerConfig(lanes=8))
    off.serve(stream)
    assert not [k for k in off.snapshot() if ".span_s." in k]
    assert len(obs.registry) == 0


def test_endpoint_spans_on_the_scheduler_registry():
    """The front door's spans (``endpoint.parse``, ``endpoint.batch``,
    ``endpoint.deliver``) sum into ``endpoint.span_s.*`` on the
    scheduler's registry, beside the scheduler's own, and deliver runs
    inside its batch."""
    from repro.core.patterns import BGP, C, TriplePattern, V
    from repro.endpoint import to_sparql
    from repro.endpoint.service import EndpointRequest, EndpointService
    from repro.rdf import TripleStore

    store = TripleStore.build(np.array([0, 0, 1, 1, 2, 2, 3, 3]),
                              np.array([0, 1, 0, 1, 0, 1, 0, 1]),
                              np.array([3, 4, 3, 5, 3, 4, 4, 5]),
                              n_terms=6, n_predicates=2)
    bgp = BGP((TriplePattern(V(0), C(0), V(1)),
               TriplePattern(V(0), C(1), V(2))), 3)
    sched = QueryScheduler(store, EngineConfig(interface="spf"))
    svc = EndpointService(sched)
    with obs.tracing() as tracer:
        resps = svc.serve([EndpointRequest(client=i % 3,
                                           sparql=to_sparql(bgp))
                           for i in range(6)])
    assert all(r.status == "ok" for r in resps)
    sums = _span_sums(tracer)
    snap = sched.snapshot()
    front = _span_s(snap, "endpoint")
    assert {"endpoint.parse", "endpoint.batch", "endpoint.deliver"} \
        <= set(front)
    for name, v in front.items():
        assert v == pytest.approx(sums[name], rel=1e-9), name
    assert "wave" in _span_s(snap, "sched")
    batches = tracer.named("endpoint.batch")
    for d in tracer.named("endpoint.deliver"):
        assert any(b["ts"] <= d["ts"] and d["ts"] + d["dur"]
                   <= b["ts"] + b["dur"] + 1e-3 for b in batches)
    obs.registry.reset()
