"""Scheduler/serial equivalence + fragment-cache behaviour.

The contract under test: the concurrent scheduler returns byte-identical
valid result rows and identical gross QueryStats to looping
``QueryEngine.run`` — across all four interfaces, all WatDiv loads, cache
on and off, with no-op padding lanes in every wave and overflow-retried
queries inside buckets — while additionally reporting exact cache savings.

The ``mesh``-named cases extend the same contract to mesh-routed waves
(``QueryScheduler(mesh=...)``): they build a mesh over every visible
device, so under the default 1-device tier-1 run they pin the shard_map
lowering itself, and under the CI matrix job's
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` they pin true
multi-device wave spanning (run ``pytest tests/test_scheduler.py -k
mesh``).
"""

import jax
import numpy as np
import pytest

from repro.core import (
    EngineConfig,
    QueryEngine,
    QueryScheduler,
    SchedulerConfig,
    interleave_clients,
    results_as_numpy,
)
from repro.rdf import generate_query_load
from repro.rdf.queries import QueryLoadConfig

LOADS = ["1-star", "2-stars", "3-stars", "paths", "union"]
INTERFACES = ["tpf", "brtpf", "spf", "endpoint"]


def _device_mesh():
    """One lane slot per visible device (1 on bare tier-1, 8 in the CI
    mesh matrix job)."""
    return jax.make_mesh((len(jax.devices()),), ("model",))


@pytest.fixture(scope="module")
def all_queries(watdiv_small):
    g, store = watdiv_small
    qs = []
    for load in LOADS:
        qs += generate_query_load(g, store, load,
                                  QueryLoadConfig(n_queries=2))
    return qs


@pytest.fixture(scope="module")
def serial_results(watdiv_small, all_queries):
    _, store = watdiv_small
    out = {}
    for iface in INTERFACES:
        eng = QueryEngine(store, EngineConfig(interface=iface, cap=2048))
        out[iface] = [eng.run(q) for q in all_queries]
    return out


def _assert_equivalent(serial, tables, stats, ctx):
    for i, (s_tbl, s_stats) in enumerate(serial):
        a = results_as_numpy(s_tbl)
        b = results_as_numpy(tables[i])
        assert a.dtype == b.dtype and a.shape == b.shape, (ctx, i)
        assert np.array_equal(a, b), (ctx, i)
        # gross stats fields (nrs..overflow) must match the serial engine
        assert tuple(int(x) for x in s_stats)[:6] \
            == tuple(int(x) for x in stats[i])[:6], (ctx, i)


@pytest.mark.parametrize("interface", INTERFACES)
def test_scheduler_byte_identical_to_serial(watdiv_small, all_queries,
                                            serial_results, interface):
    """All loads through one scheduler, cache off and on: identical valid
    rows and gross stats.  lanes=4 forces multi-wave buckets plus padding
    lanes in the final (and any underfull) wave of each bucket."""
    _, store = watdiv_small
    cfg = EngineConfig(interface=interface, cap=2048)
    for use_cache in (False, True):
        sched = QueryScheduler(store, cfg,
                               SchedulerConfig(lanes=4, use_cache=use_cache))
        tables, stats = sched.run_queries(all_queries)
        _assert_equivalent(serial_results[interface], tables, stats,
                           (interface, use_cache))
        if not use_cache:
            assert sched.cache.stats.total_hits == 0
            assert all(int(s.cache_hits) == 0 and int(s.nrs_saved) == 0
                       for s in stats)


def test_padding_lanes_are_noops(watdiv_small, serial_results, all_queries):
    """Three copies of one query with collapsing off form a 3-job bucket,
    which a power-of-two wave pads to 4 lanes; the padded no-op lane must
    not contribute results or change the active lanes' bytes."""
    _, store = watdiv_small
    qs = [all_queries[0]] * 3
    cfg = EngineConfig(interface="spf", cap=2048)
    sched = QueryScheduler(store, cfg,
                           SchedulerConfig(lanes=8, collapse_duplicates=False))
    tables, stats = sched.run_queries(qs)
    _assert_equivalent([serial_results["spf"][0]] * 3, tables, stats,
                       "padding")
    m = sched.metrics
    assert m.jobs == 3  # collapsing disabled: one lane per request
    assert m.lane_steps > m.active_lane_steps  # padding actually happened
    assert m.pad_fraction > 0


def test_overflow_retry_inside_bucket(watdiv_small):
    """Queries that overflow the starting capacity are retried at 4x inside
    the scheduler — resumably: re-bucketed at the larger cap *at the
    failing unit*, seeded with the checkpointed table — and still match
    the serial engine's retry ladder byte-for-byte.  The blind config
    (``capacity_planner=False``) forces the ladder; with the planner on,
    the same load starts at oracle rungs and never overflows at all."""
    g, store = watdiv_small
    qs = generate_query_load(g, store, "2-stars", QueryLoadConfig(n_queries=3))
    cfg = EngineConfig(interface="spf", cap=4, capacity_planner=False)
    eng = QueryEngine(store, cfg)
    serial = [eng.run(q) for q in qs]
    for use_cache in (False, True):
        sched = QueryScheduler(store, cfg,
                               SchedulerConfig(lanes=4, use_cache=use_cache))
        tables, stats = sched.run_queries(qs)
        _assert_equivalent(serial, tables, stats, ("overflow", use_cache))
        assert sched.metrics.retries > 0
    # planner on: data-informed starting rungs make overflow rare (here:
    # absent), with byte-identical results
    planned_cfg = EngineConfig(interface="spf", cap=4)
    sched = QueryScheduler(store, planned_cfg, SchedulerConfig(lanes=4))
    tables, stats = sched.run_queries(qs)
    _assert_equivalent(serial, tables, stats, "planner-on")
    assert sched.metrics.retries == 0


def test_cross_client_requests_hit_the_cache(watdiv_small):
    """N simulated clients issuing the same load: duplicates collapse onto
    shared executions, the cache reports the hits, and the per-request
    stats carry exact NRS/NTB savings while gross fields stay identical."""
    g, store = watdiv_small
    qs = generate_query_load(g, store, "2-stars", QueryLoadConfig(n_queries=2))
    n_clients = 4
    cfg = EngineConfig(interface="spf", cap=2048)
    sched = QueryScheduler(store, cfg, SchedulerConfig(lanes=8))
    served = sched.serve(interleave_clients(qs, n_clients))
    assert len(served) == len(qs) * n_clients
    eng = QueryEngine(store, cfg)
    for i, (tbl, stats) in enumerate(served):
        ref_tbl, ref_stats = eng.run(qs[i // n_clients])
        assert np.array_equal(results_as_numpy(tbl),
                              results_as_numpy(ref_tbl)), i
        assert tuple(int(x) for x in stats)[:6] \
            == tuple(int(x) for x in ref_stats)[:6], i
    assert sched.cache.stats.hit_rate > 0
    # every duplicate request is fully cache-served
    dup_stats = [st for i, (_, st) in enumerate(served) if i % n_clients]
    assert all(int(s.nrs_saved) == int(s.nrs) for s in dup_stats)
    assert all(int(s.ntb_saved) == int(s.ntb) for s in dup_stats)
    assert all(int(s.cache_misses) == 0 for s in dup_stats)
    # primaries computed their units against the store
    primaries = [st for i, (_, st) in enumerate(served) if i % n_clients == 0]
    assert all(int(s.cache_misses) > 0 for s in primaries)


def test_engine_run_load_delegates_to_scheduler(watdiv_small, all_queries,
                                                serial_results):
    """The public load path goes through the scheduler and stays equivalent
    to looping ``run`` (with cache fields now populated)."""
    _, store = watdiv_small
    eng = QueryEngine(store, EngineConfig(interface="spf", cap=2048))
    qs = all_queries[:4]
    tables, stats = eng.run_load(qs)
    _assert_equivalent(serial_results["spf"][:4], tables, stats, "run_load")


# --------------------------------------------------------------------------
# mesh-routed waves (run `-k mesh`; the CI matrix job forces 8 host devices)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("interface", INTERFACES)
def test_mesh_waves_byte_identical_to_serial(watdiv_small, all_queries,
                                             serial_results, interface):
    """Mesh-routed scheduler waves: one request per device lane (collapsing
    off, stream interleaved over as many clients as devices) so wave width
    reaches the mesh's lane-slot count and the shard_map step engages —
    valid rows and gross stats must stay byte-identical to the serial
    path, cache off and on."""
    _, store = watdiv_small
    n_dev = len(jax.devices())
    qs = all_queries[:4]  # 1-star + 2-stars samples
    cfg = EngineConfig(interface=interface, cap=2048)
    for use_cache in (False, True):
        sched = QueryScheduler(
            store, cfg,
            SchedulerConfig(lanes=8, use_cache=use_cache,
                            collapse_duplicates=False),
            mesh=_device_mesh())
        served = sched.serve(interleave_clients(qs, n_dev))
        serial = [serial_results[interface][i // n_dev]
                  for i in range(len(served))]
        _assert_equivalent(serial, [t for t, _ in served],
                           [s for _, s in served],
                           ("mesh", interface, use_cache))
        # full-width buckets: every dispatched step spanned the mesh
        assert sched.metrics.mesh_steps > 0 or sched.metrics.steps == 0
        if not use_cache:
            assert sched.metrics.mesh_steps == sched.metrics.steps > 0


def test_mesh_vmap_mixed_widths_and_retries(watdiv_small):
    """One bucket wide enough for the mesh plus a 1-job bucket and a
    low starting cap: the scheduler mixes mesh waves, vmap fallback waves
    (on multi-device meshes) and in-bucket 4x retries — all byte-identical
    to the serial retry ladder."""
    g, store = watdiv_small
    n_dev = len(jax.devices())
    qs = generate_query_load(g, store, "2-stars", QueryLoadConfig(n_queries=2))
    single = generate_query_load(g, store, "1-star", QueryLoadConfig(n_queries=1))
    # blind config: the retry ladder is the subject under test here
    cfg = EngineConfig(interface="spf", cap=4, capacity_planner=False)
    eng = QueryEngine(store, cfg)
    serial = {id(q): eng.run(q) for q in qs + single}
    stream = [(c, q) for q in qs for c in range(n_dev)] \
        + [(0, single[0])]
    sched = QueryScheduler(
        store, cfg, SchedulerConfig(lanes=8, collapse_duplicates=False),
        mesh=_device_mesh())
    served = sched.serve(stream)
    for (c, q), (tbl, stats) in zip(stream, served):
        ref_tbl, ref_stats = serial[id(q)]
        assert np.array_equal(results_as_numpy(tbl),
                              results_as_numpy(ref_tbl))
        assert tuple(int(x) for x in stats)[:6] \
            == tuple(int(x) for x in ref_stats)[:6]
    m = sched.metrics
    assert m.retries > 0 and m.mesh_steps > 0
    if n_dev > 1:
        # the 1-job bucket is narrower than the lane slots: vmap fallback
        assert m.steps > m.mesh_steps


def test_mesh_pod_shared_cache_and_run_load(watdiv_small):
    """DistributedEngine.run_load routes the load through a mesh scheduler
    sharing the engine's pod cache: results match serial, and a second
    scheduler on the same pod cache is served from the first one's
    fragments."""
    from repro.core.distributed import DistConfig, DistributedEngine

    g, store = watdiv_small
    qs = generate_query_load(g, store, "2-stars", QueryLoadConfig(n_queries=2))
    mesh = _device_mesh()
    cfg = EngineConfig(interface="spf", cap=2048)
    eng = DistributedEngine(store, jax.make_mesh((1, 1), ("data", "model")),
                            cfg, DistConfig(cap=2048, shard_cap=512))
    eng.mesh = mesh  # lane mesh for the scheduler path
    tables, stats = eng.run_load(qs)
    serial = QueryEngine(store, cfg)
    for q, tbl, st in zip(qs, tables, stats):
        ref_tbl, ref_stats = serial.run(q)
        assert np.array_equal(results_as_numpy(tbl),
                              results_as_numpy(ref_tbl))
        assert tuple(int(x) for x in st)[:6] \
            == tuple(int(x) for x in ref_stats)[:6]
    assert eng.pod_cache.stats.insertions + eng.pod_cache.stats.neg_insertions > 0
    # a fresh scheduler on the same pod cache: fully fragment-served
    sched2 = QueryScheduler(store, cfg, cache=eng.pod_cache, mesh=mesh)
    _, stats2 = sched2.run_queries(qs)
    assert all(int(s.cache_misses) == 0 and int(s.cache_hits) > 0
               for s in stats2)
    assert all(int(s.nrs_saved) == int(s.nrs) for s in stats2)


# --------------------------------------------------------------------------
# sharded-store waves (run `-k shard`; multi-shard counts need multiple
# devices — the CI dist-sched job forces 8; bare tier-1 covers n_shards=1)
# --------------------------------------------------------------------------

def _shard_meshes():
    """(n_shards, lane_slots, mesh) for every shard count in {1, 2, 4} the
    visible device count supports: the store shards along ``data`` and
    wave lanes span ``model``."""
    n_dev = len(jax.devices())
    out = []
    for s in (1, 2, 4):
        if s <= n_dev and n_dev % s == 0:
            out.append((s, n_dev // s,
                        jax.make_mesh((s, n_dev // s), ("data", "model"))))
    return out


@pytest.mark.parametrize("interface", INTERFACES)
def test_sharded_waves_byte_identical_to_serial(watdiv_small, all_queries,
                                                serial_results, interface):
    """Sharded scheduler waves (store subject-hash sharded along ``data``,
    lanes along ``model``) must return byte-identical valid rows and gross
    stats to the serial path — across shard counts, cache on and off.
    The stream is interleaved wide enough to cover the lane slots so the
    sharded lowering actually engages."""
    _, store = watdiv_small
    qs = all_queries[:4]
    cfg = EngineConfig(interface=interface, cap=2048)
    for n_shards, slots, mesh in _shard_meshes():
        for use_cache in (False, True):
            sched = QueryScheduler(
                store, cfg,
                SchedulerConfig(lanes=8, use_cache=use_cache,
                                collapse_duplicates=False),
                mesh=mesh, data_axis="data")
            served = sched.serve(interleave_clients(qs, slots))
            serial = [serial_results[interface][i // slots]
                      for i in range(len(served))]
            _assert_equivalent(serial, [t for t, _ in served],
                               [s for _, s in served],
                               ("shard", interface, n_shards, use_cache))
            assert sched.metrics.shard_steps > 0 or sched.metrics.steps == 0
            if not use_cache:
                assert sched.metrics.shard_steps == sched.metrics.steps > 0
                assert sched.metrics.gather_bytes > 0


def test_sharded_overflow_resume_byte_identical(watdiv_small):
    """Forced overflow on the sharded lowering: a tiny starting capacity
    drives resumable 4x retries (re-entering at the failing unit with the
    checkpointed seed), and the retry sequence's final results must match
    the serial blind ladder byte-for-byte.  Overflow on the sharded step
    is derived from *global* expansion totals, so retries fire in
    lockstep with the serial path even when every local shard fit."""
    g, store = watdiv_small
    qs = generate_query_load(g, store, "2-stars", QueryLoadConfig(n_queries=3))
    cfg = EngineConfig(interface="spf", cap=4, capacity_planner=False)
    eng = QueryEngine(store, cfg)
    serial = [eng.run(q) for q in qs]
    for n_shards, slots, mesh in _shard_meshes():
        for use_cache in (False, True):
            sched = QueryScheduler(
                store, cfg,
                SchedulerConfig(lanes=8, use_cache=use_cache,
                                collapse_duplicates=False),
                mesh=mesh, data_axis="data")
            served = sched.serve(interleave_clients(qs, slots))
            serial_ref = [serial[i // slots] for i in range(len(served))]
            _assert_equivalent(serial_ref, [t for t, _ in served],
                               [s for _, s in served],
                               ("shard-ovf", n_shards, use_cache))
            assert sched.metrics.retries > 0


def _shard_meshes_wide():
    """_shard_meshes plus the all-devices-sharded extreme (8 shards x 1
    lane slot in the CI dist-sched job)."""
    n_dev = len(jax.devices())
    out = list(_shard_meshes())
    if n_dev >= 8 and n_dev % 8 == 0:
        out.append((8, n_dev // 8,
                    jax.make_mesh((8, n_dev // 8), ("data", "model"))))
    return out


def test_sharded_merge_strategies_byte_identical(watdiv_small, all_queries,
                                                 serial_results):
    """The k-way shard merge vs the replicated lexsort (and the auto
    pick): identical bytes to each other AND to the serial path at every
    shard count in {1, 2, 4, 8} the device count supports.  The merge is
    pure placement of the order-restoring work — nothing downstream may
    be able to tell which one ran."""
    _, store = watdiv_small
    qs = all_queries[:4]
    cfg = EngineConfig(interface="spf", cap=2048)
    for n_shards, slots, mesh in _shard_meshes_wide():
        outs = {}
        for merge in ("kway", "lexsort", "auto"):
            sched = QueryScheduler(
                store, cfg,
                SchedulerConfig(lanes=8, collapse_duplicates=False,
                                shard_merge=merge),
                mesh=mesh, data_axis="data")
            served = sched.serve(interleave_clients(qs, slots))
            outs[merge] = served
            serial = [serial_results["spf"][i // slots]
                      for i in range(len(served))]
            _assert_equivalent(serial, [t for t, _ in served],
                               [s for _, s in served],
                               ("shard-merge", merge, n_shards))
        for merge in ("lexsort", "auto"):
            for (t_k, _), (t_o, _) in zip(outs["kway"], outs[merge]):
                assert np.array_equal(results_as_numpy(t_k),
                                      results_as_numpy(t_o)), \
                    (merge, n_shards)


def test_sharded_latch_rung_waves_byte_identical(watdiv_small):
    """Waves AT the overflow-latch rung (cap == max_cap) now stay on the
    sharded lowering: the step's latch mode merges in global serial row
    order after every branch, so the truncated table, latched overflow
    flag and gross stats must match the serial give-up rung byte-for-byte
    — under both merge strategies and shard counts up to 8."""
    g, store = watdiv_small
    from repro.rdf import generate_query_load
    from repro.rdf.queries import QueryLoadConfig

    qs = generate_query_load(g, store, "2-stars",
                             QueryLoadConfig(n_queries=3))
    cfg = EngineConfig(interface="spf", cap=8, max_cap=32,
                       capacity_planner=False)
    eng = QueryEngine(store, cfg)
    serial = [eng.run(q) for q in qs]
    assert any(bool(s.overflow) for _, s in serial), \
        "fixture queries must actually latch for this test to bite"
    for n_shards, slots, mesh in _shard_meshes_wide():
        for merge in ("kway", "lexsort"):
            sched = QueryScheduler(
                store, cfg,
                SchedulerConfig(lanes=8, collapse_duplicates=False,
                                shard_merge=merge),
                mesh=mesh, data_axis="data")
            served = sched.serve(interleave_clients(qs, slots))
            serial_ref = [serial[i // slots] for i in range(len(served))]
            _assert_equivalent(serial_ref, [t for t, _ in served],
                               [s for _, s in served],
                               ("shard-latch", merge, n_shards))
            # the latch waves themselves must have run sharded
            assert sched.metrics.shard_steps == sched.metrics.steps > 0


def test_sharded_occupancy_trim_warm_waves_byte_identical(watdiv_small,
                                                          all_queries,
                                                          serial_results):
    """Occupancy-fed gather trims: a warm re-serve consults the planner's
    observed shard peaks (pow2-rounded) instead of the static headroom
    budget, and the results must stay byte-identical — an observed peak
    is exact for a deterministic re-run, and any undershoot would ride
    the overflow-retry path rather than corrupt bytes."""
    _, store = watdiv_small
    qs = all_queries[:4]
    cfg = EngineConfig(interface="spf", cap=2048)
    for n_shards, slots, mesh in _shard_meshes():
        sched = QueryScheduler(
            store, cfg,
            SchedulerConfig(lanes=8, use_cache=False,
                            collapse_duplicates=False),
            mesh=mesh, data_axis="data")
        for _ in range(2):  # pass 2 runs with warm shard-peak hints
            served = sched.serve(interleave_clients(qs, slots))
            serial = [serial_results["spf"][i // slots]
                      for i in range(len(served))]
            _assert_equivalent(serial, [t for t, _ in served],
                               [s for _, s in served],
                               ("shard-trim", n_shards))
        if sched.metrics.shard_steps:
            # the sharded units were observed: hints exist for unit 0
            plan = sched._plan(qs[0])
            assert sched.planner.shard_peak_hint(plan, 0, n_shards) \
                is not None


def test_shard_merge_blocks_kway_matches_lexsort():
    """Unit-level pin of the merge mechanics (no mesh): pairwise
    rank-based merges of pre-sorted blocks — including trim-truncated
    blocks with blanked invalid tails — reproduce the full lexsort
    byte-for-byte, invalid regions included."""
    from repro.core import stepper

    rng = np.random.default_rng(7)
    sort_cols = (0, 1)
    for n_blocks, trim_frac in [(2, 1.0), (4, 1.0), (8, 1.0), (4, 0.5)]:
        cap = 64
        trim = int(cap * trim_frac)
        blocks = []
        base = 0
        for _ in range(n_blocks):
            n_valid = int(rng.integers(0, cap + 1))
            rows = np.full((cap, 3), -1, np.int32)
            # unique (c0, c1) keys, sorted within the block
            rows[:n_valid, 0] = np.sort(rng.integers(0, 40, n_valid))
            rows[:n_valid, 1] = base + np.arange(n_valid)
            rows[:n_valid, 2] = rng.integers(0, 1000, n_valid)
            base += n_valid
            valid = np.arange(cap) < n_valid
            blocks.append((rows, valid))
        trimmed = [stepper._trim_block(jax.numpy.asarray(r),
                                       jax.numpy.asarray(v), trim)
                   for r, v in blocks]
        lost_any = any(bool(l) for _, _, l in trimmed)
        assert lost_any == any(v[trim:].any() for _, v in blocks)
        gathered_r = np.concatenate([np.asarray(r) for r, _, _ in trimmed])
        gathered_v = np.concatenate([np.asarray(v) for _, v, _ in trimmed])
        want_r, want_v = stepper.lexsort_rows(jax.numpy.asarray(gathered_r),
                                              jax.numpy.asarray(gathered_v),
                                              sort_cols)
        acc_r, acc_v, _ = trimmed[0]
        for r, v, _ in trimmed[1:]:
            acc_r, acc_v = stepper.merge_sorted_blocks(acc_r, acc_v, r, v,
                                                       sort_cols)
        np.testing.assert_array_equal(np.asarray(acc_r), np.asarray(want_r))
        np.testing.assert_array_equal(np.asarray(acc_v), np.asarray(want_v))


def test_shard_merge_blocks_nonpow2_padded_schedule():
    """Unit-level pin of the *padded* non-pow2 merge schedule (no mesh):
    fold the ``rem = n - base`` extra blocks into the low devices with a
    pre-round merge (empty partner for devices without an extra), then
    tree-merge the power-of-two core — the exact block dataflow
    ``stepper.gather_merge_kway`` runs via ppermute.  The valid prefix
    must reproduce the full lexsort byte-for-byte and the overhang must
    be all-invalid."""
    from repro.core import stepper

    jnp = jax.numpy
    rng = np.random.default_rng(11)
    sort_cols = (0, 1)
    for n_blocks in (3, 5, 6):
        cap = 64
        trim = 32  # truncating trim: lost rows + blanked tails in play
        blocks = []
        base_id = 0
        for _ in range(n_blocks):
            n_valid = int(rng.integers(0, cap + 1))
            rows = np.full((cap, 3), -1, np.int32)
            rows[:n_valid, 0] = np.sort(rng.integers(0, 40, n_valid))
            rows[:n_valid, 1] = base_id + np.arange(n_valid)
            rows[:n_valid, 2] = rng.integers(0, 1000, n_valid)
            base_id += n_valid
            valid = np.arange(cap) < n_valid
            blocks.append((rows, valid))
        trimmed = [stepper._trim_block(jnp.asarray(r), jnp.asarray(v), trim)
                   for r, v in blocks]
        base_n = 1 << (n_blocks.bit_length() - 1)
        rem = n_blocks - base_n
        assert rem > 0 or n_blocks == base_n
        empty_r = jnp.full((trim, 3), -1, jnp.int32)
        empty_v = jnp.zeros((trim,), bool)
        # pre-round: fold extras into devices 0..rem-1, empty partners
        # for the rest (what a non-recipient's re-blanked zeros become)
        eff = [stepper.merge_sorted_blocks(
                   trimmed[i][0], trimmed[i][1],
                   trimmed[base_n + i][0] if i < rem else empty_r,
                   trimmed[base_n + i][1] if i < rem else empty_v,
                   sort_cols)
               for i in range(base_n)]
        # pow2 core: tree merge (content-equivalent to recursive doubling)
        while len(eff) > 1:
            eff = [stepper.merge_sorted_blocks(eff[2 * i][0], eff[2 * i][1],
                                               eff[2 * i + 1][0],
                                               eff[2 * i + 1][1], sort_cols)
                   for i in range(len(eff) // 2)]
        got_r, got_v = np.asarray(eff[0][0]), np.asarray(eff[0][1])
        gathered_r = np.concatenate([np.asarray(r) for r, _, _ in trimmed])
        gathered_v = np.concatenate([np.asarray(v) for _, v, _ in trimmed])
        want_r, want_v = stepper.lexsort_rows(jnp.asarray(gathered_r),
                                              jnp.asarray(gathered_v),
                                              sort_cols)
        n_g = n_blocks * trim
        assert got_r.shape[0] >= n_g, n_blocks
        np.testing.assert_array_equal(got_r[:n_g], np.asarray(want_r))
        np.testing.assert_array_equal(got_v[:n_g], np.asarray(want_v))
        assert not got_v[n_g:].any(), n_blocks


def test_sharded_nonpow2_shard_counts_byte_identical(watdiv_small,
                                                     all_queries,
                                                     serial_results):
    """Non-power-of-two shard counts run the padded k-way schedule (no
    lexsort fallback) and stay byte-identical to the serial path.  Runs
    a data=3 x model=2 mesh over six devices (and data=6 x model=1), so
    it needs >= 6 visible devices — the CI dist job's forced-host-device
    run."""
    n_dev = len(jax.devices())
    if n_dev < 6:
        pytest.skip("needs >= 6 devices for a non-pow2 shard axis")
    _, store = watdiv_small
    qs = all_queries[:4]
    cfg = EngineConfig(interface="spf", cap=2048)
    meshes = [(3, 2, jax.sharding.Mesh(
                  np.array(jax.devices()[:6]).reshape(3, 2),
                  ("data", "model")))]
    meshes.append((6, 1, jax.sharding.Mesh(
        np.array(jax.devices()[:6]).reshape(6, 1), ("data", "model"))))
    for n_shards, slots, mesh in meshes:
        for merge in ("auto", "kway", "lexsort"):
            sched = QueryScheduler(
                store, cfg,
                SchedulerConfig(lanes=8, collapse_duplicates=False,
                                shard_merge=merge),
                mesh=mesh, data_axis="data")
            served = sched.serve(interleave_clients(qs, slots))
            serial = [serial_results["spf"][i // slots]
                      for i in range(len(served))]
            _assert_equivalent(serial, [t for t, _ in served],
                               [s for _, s in served],
                               ("shard-nonpow2", merge, n_shards))
            assert sched.metrics.shard_steps > 0, (merge, n_shards)
            if merge == "lexsort":
                # the explicit fallback is never silent
                assert sched.metrics.merge_lexsort_steps > 0
            else:
                assert sched.metrics.merge_lexsort_steps == 0


def test_shard_count_invariant_digests_share_cache(watdiv_small, all_queries,
                                                   serial_results):
    """``fingerprint_rows`` digests are a pure function of the valid
    prefix, which is byte-identical across lowerings and shard counts —
    so a cache filled by a vmap scheduler fully serves sharded schedulers
    at every shard count (zero misses), and vice versa."""
    from repro.core import FragmentCache

    _, store = watdiv_small
    qs = all_queries[:4]
    cfg = EngineConfig(interface="spf", cap=2048)
    cache = FragmentCache()
    filler = QueryScheduler(store, cfg,
                            SchedulerConfig(lanes=8, cap_hints=False),
                            cache=cache)
    filler.run_queries(qs)
    assert cache.stats.insertions + cache.stats.neg_insertions > 0
    for n_shards, _, mesh in _shard_meshes():
        sched = QueryScheduler(store, cfg,
                               SchedulerConfig(lanes=8, cap_hints=False),
                               cache=cache, mesh=mesh, data_axis="data")
        tables, stats = sched.run_queries(qs)
        assert all(int(s.cache_misses) == 0 and int(s.cache_hits) > 0
                   for s in stats), n_shards
        for i, tbl in enumerate(tables):
            assert np.array_equal(
                results_as_numpy(tbl),
                results_as_numpy(serial_results["spf"][i][0])), (n_shards, i)


def test_all_hit_wave_zero_host_materializations(watdiv_small, all_queries):
    """The device-replay invariant: re-serving an identical load through a
    warm scheduler serves every unit step from the cache, replays the
    deltas on device, and performs ZERO host Omega-block materialisations
    (``SchedMetrics.host_block_pulls`` — the counting hook; the only
    end-of-wave pull is the response delivery, which is not counted)."""
    _, store = watdiv_small
    qs = all_queries[:4]
    cfg = EngineConfig(interface="spf", cap=2048)
    # cap_hints off: stable capacities keep the cache keys identical
    # across passes, so the second pass is all-hit by construction
    sched = QueryScheduler(store, cfg,
                           SchedulerConfig(lanes=8, cap_hints=False))
    first_tables, _ = sched.run_queries(qs)
    assert sched.metrics.host_block_pulls > 0  # misses recorded deltas
    steps0 = sched.metrics.steps
    pulls0 = sched.metrics.host_block_pulls
    skipped0 = sched.metrics.steps_skipped
    tables, stats = sched.run_queries(qs)
    assert sched.metrics.steps == steps0, "all-hit pass dispatched steps"
    assert sched.metrics.host_block_pulls == pulls0, \
        "all-hit pass materialised Omega blocks on the host"
    assert sched.metrics.wave_pulls == steps0, "all-hit pass pulled a wave"
    assert sched.metrics.steps_skipped > skipped0
    assert all(int(s.cache_misses) == 0 and int(s.cache_hits) > 0
               for s in stats)
    for a, b in zip(first_tables, tables):
        assert np.array_equal(results_as_numpy(a), results_as_numpy(b))


# --------------------------------------------------------------------------
# a unit step's outputs reach the host in one transfer (WAVE_PULL_BYTES)
# --------------------------------------------------------------------------

LOWERINGS = ["vmap", "mesh", "shard"]
# either side of the scheduler's byte budget: every wave pulled whole, or
# every missed lane pulled on its own
BUDGETS = {"wave_pull": 1 << 40, "lane_pulls": 0}


@pytest.fixture(scope="module")
def budget_serves(watdiv_small, all_queries):
    """Serve one interleaved load per (lowering, side of the byte budget),
    with the module constant patched for the serve; memoised."""
    from repro.core import scheduler as sched_mod

    _, store = watdiv_small
    qs = all_queries[:6]
    runs = {}

    def serve(lowering: str, side: str):
        if (lowering, side) not in runs:
            mesh, kw = None, {}
            if lowering == "mesh":
                mesh = _device_mesh()
            elif lowering == "shard":
                mesh, kw = _shard_meshes()[0][2], {"data_axis": "data"}
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(sched_mod, "WAVE_PULL_BYTES", BUDGETS[side])
                sched = QueryScheduler(
                    store, EngineConfig(interface="spf", cap=2048),
                    SchedulerConfig(lanes=8, cap_hints=False,
                                    collapse_duplicates=False),
                    mesh=mesh, **kw)
                served = sched.serve(interleave_clients(qs, 2))
            runs[lowering, side] = (sched, [t for t, _ in served])
        return runs[lowering, side]

    return serve


@pytest.mark.parametrize("lowering", LOWERINGS)
def test_wave_pull_records_what_lane_pulls_record(budget_serves, lowering):
    """Under the byte budget a step's rows come to the host whole, in its
    one transfer, and each miss is sliced from them; past it each missed
    lane pulls its own prefix.  Both record byte-identical fragments (same
    keys, LRU order, arrays, dtypes and fields) and serve identical rows;
    the per-lane side costs two more host syncs per recorded lane."""
    whole, t_whole = budget_serves(lowering, "wave_pull")
    lanes, t_lanes = budget_serves(lowering, "lane_pulls")
    wm, lm = whole.metrics, lanes.metrics
    assert wm.steps == lm.steps > 0
    if lowering == "shard":
        assert wm.shard_steps == wm.steps
    elif lowering == "mesh":
        assert wm.mesh_steps == wm.steps
    assert wm.wave_pulls == wm.steps and lm.wave_pulls == 0
    assert wm.host_block_pulls == lm.host_block_pulls > wm.retries
    recorded = wm.host_block_pulls - wm.retries
    assert lm.host_syncs - wm.host_syncs == 2 * recorded
    for a, b in zip(t_whole, t_lanes, strict=True):
        assert np.array_equal(results_as_numpy(a), results_as_numpy(b))
    (pos_w, neg_w), (pos_l, neg_l) = (whole.cache.export_state(),
                                      lanes.cache.export_state())
    assert pos_w and neg_w == neg_l
    assert [k for k, _ in pos_w] == [k for k, _ in pos_l]
    for (_, ew), (_, el) in zip(pos_w, pos_l):
        for f in ("src_row", "written"):
            x, y = getattr(ew, f), getattr(el, f)
            assert x.dtype == y.dtype and x.shape == y.shape, f
            assert x.tobytes() == y.tobytes(), f
        assert ew._replace(src_row=None, written=None) \
            == el._replace(src_row=None, written=None)


@pytest.mark.parametrize("side", list(BUDGETS))
def test_cached_fragments_own_their_bytes(budget_serves, side):
    """Every stored fragment owns its memory (a copy, never a view that
    would keep a whole wave alive on the host), so ``bytes_stored`` is
    the sum of the entries' own bytes."""
    sched, _ = budget_serves("vmap", side)
    entries, _ = sched.cache.export_state()
    assert entries
    for _, e in entries:
        for a in (e.src_row, e.written):
            assert a.flags.owndata and a.flags.c_contiguous
            assert a.base is None
    assert sched.cache.stats.bytes_stored == sum(e.nbytes
                                                 for _, e in entries)


def test_mixed_signature_distributed_batch(watdiv_small):
    """run_batch no longer refuses plan-heterogeneous batches: it buckets
    by signature internally (1x1 mesh keeps this in-process)."""
    import jax

    from repro.core.distributed import DistConfig, DistributedEngine

    g, store = watdiv_small
    qs = (generate_query_load(g, store, "2-stars", QueryLoadConfig(n_queries=2))
          + generate_query_load(g, store, "paths", QueryLoadConfig(n_queries=1)))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    eng = DistributedEngine(store, mesh, EngineConfig(interface="spf"),
                            DistConfig(cap=2048, shard_cap=512))
    rows, valid, stats = eng.run_batch(qs)
    assert len(rows) == len(qs)
    serial = QueryEngine(store, EngineConfig(interface="spf", cap=2048))
    for i, q in enumerate(qs):
        ref = results_as_numpy(serial.run(q)[0])
        got = np.asarray(rows[i])[np.asarray(valid[i])]
        assert set(map(tuple, got)) == set(map(tuple, ref)), i
