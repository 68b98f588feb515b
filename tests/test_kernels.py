"""Pallas kernels vs jnp oracles: shape/dtype sweeps in interpret mode."""

import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.owned_probe import (MAX_SHARDS, eqrange_owned_pallas,
                                       shard_of_limbs)
from repro.kernels.run_probe import (run_probe_pallas,
                                     run_probe_prefetch_pallas)
from repro.kernels.sorted_probe import sorted_probe_pallas


# --------------------------------------------------------------- sorted_probe

@pytest.mark.parametrize("n,q,dt", [
    (1000, 77, np.int32), (5000, 256, np.int64), (131, 513, np.int32),
    (2048, 2048, np.int64), (1, 1, np.int32), (10, 4096, np.int64),
])
def test_sorted_probe_sweep(n, q, dt, rng):
    keys = np.sort(rng.integers(0, max(n * 3, 10), n)).astype(dt)
    queries = rng.integers(-5, max(n * 3, 10) + 5, q).astype(dt)
    r_lo, r_hi, c1 = sorted_probe_pallas(jnp.asarray(keys),
                                         jnp.asarray(queries),
                                         interpret=True)
    np.testing.assert_array_equal(np.asarray(r_lo),
                                  np.searchsorted(keys, queries, "left"))
    np.testing.assert_array_equal(np.asarray(r_hi),
                                  np.searchsorted(keys, queries, "right"))
    r2, c2 = ref.sorted_probe_ref(jnp.asarray(keys), jnp.asarray(queries))
    np.testing.assert_array_equal(np.asarray(r_lo), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


@given(st.data())
@settings(max_examples=20, deadline=None)
def test_sorted_probe_property(data):
    n = data.draw(st.integers(1, 300))
    q = data.draw(st.integers(1, 100))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    keys = np.sort(rng.integers(0, 100, n)).astype(np.int64)
    queries = rng.integers(-10, 110, q).astype(np.int64)
    r_lo, r_hi, c1 = sorted_probe_pallas(jnp.asarray(keys),
                                         jnp.asarray(queries),
                                         q_tile=64, k_tile=128,
                                         interpret=True)
    np.testing.assert_array_equal(
        np.asarray(r_lo), np.searchsorted(keys, queries, "left"))
    np.testing.assert_array_equal(
        np.asarray(r_hi), np.searchsorted(keys, queries, "right"))
    np.testing.assert_array_equal(
        np.asarray(c1), np.isin(queries, keys))


@pytest.mark.parametrize("dt", [np.int32, np.int64])
@pytest.mark.parametrize("max_in_keys", [False, True])
def test_sorted_probe_dtype_max_query(dt, max_in_keys):
    """A query equal to the dtype max must not see the +max key padding:
    rank_hi stays <= n and contains reflects the real keys only."""
    maxv = np.iinfo(dt).max
    keys = np.array([1, 5, 9] + ([maxv] if max_in_keys else []), dt)
    queries = np.array([maxv, 5, maxv - 1], dt)
    r_lo, r_hi, c = sorted_probe_pallas(jnp.asarray(keys),
                                        jnp.asarray(queries),
                                        interpret=True)
    np.testing.assert_array_equal(np.asarray(r_lo),
                                  np.searchsorted(keys, queries, "left"))
    np.testing.assert_array_equal(np.asarray(r_hi),
                                  np.searchsorted(keys, queries, "right"))
    np.testing.assert_array_equal(np.asarray(c), np.isin(queries, keys))


# ----------------------------------------------------------------- run_probe

def _run_probe_truth(vals, lo, hi, targets):
    pos = np.array([l + np.searchsorted(vals[l:h], t, "left")
                    for l, h, t in zip(lo, hi, targets)])
    contains = np.array([t in vals[l:h].tolist()
                         for l, h, t in zip(lo, hi, targets)])
    return pos, contains


def _check_run_probe(vals, lo, hi, targets, **tiles):
    p1, c1 = run_probe_pallas(jnp.asarray(vals), jnp.asarray(lo),
                              jnp.asarray(hi), jnp.asarray(targets),
                              interpret=True, **tiles)
    p2, c2 = ref.run_probe_ref(jnp.asarray(vals), jnp.asarray(lo),
                               jnp.asarray(hi), jnp.asarray(targets))
    want_p, want_c = _run_probe_truth(vals, lo, hi, targets)
    np.testing.assert_array_equal(np.asarray(p1), want_p)
    np.testing.assert_array_equal(np.asarray(c1), want_c)
    np.testing.assert_array_equal(np.asarray(p2), want_p)
    np.testing.assert_array_equal(np.asarray(c2), want_c)


@pytest.mark.parametrize("n,r,dt", [
    (1000, 77, np.int32), (5000, 300, np.int64), (131, 513, np.int32),
    (2048, 256, np.int64), (1, 1, np.int32), (10, 4096, np.int64),
])
def test_run_probe_sweep(n, r, dt, rng):
    # one globally sorted array => every window [lo, hi) is a sorted run
    vals = np.sort(rng.integers(0, max(n * 3, 10), n)).astype(dt)
    lo = rng.integers(0, n + 1, r)
    hi = np.minimum(n, lo + rng.integers(0, n + 1, r))
    targets = rng.integers(-5, max(n * 3, 10) + 5, r).astype(dt)
    _check_run_probe(vals, lo, hi, targets)


def test_run_probe_empty_runs(rng):
    vals = np.sort(rng.integers(0, 50, 64)).astype(np.int32)
    lo = np.array([0, 10, 64, 32], np.int64)
    hi = lo.copy()  # all runs empty
    targets = np.array([0, 25, 49, -1], np.int32)
    p, c = run_probe_pallas(jnp.asarray(vals), jnp.asarray(lo),
                            jnp.asarray(hi), jnp.asarray(targets),
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(p), lo)  # pos degenerates to lo
    assert not np.asarray(c).any()


def test_run_probe_boundary_runs(rng):
    """Runs touching index 0 and index n, and the full-array run."""
    n = 300
    vals = np.sort(rng.integers(0, 500, n)).astype(np.int64)
    lo = np.array([0, 0, n - 7, 0, 17], np.int64)
    hi = np.array([5, n, n, n, n], np.int64)
    targets = np.array([vals[0], vals[-1], vals[-1], -10**9, vals[20]],
                       np.int64)
    _check_run_probe(vals, lo, hi, targets)


def test_run_probe_padding_edges():
    """Max-valued targets and non-tile-multiple shapes: the +max value
    padding and the [0, 0) row padding must stay inert."""
    maxv = np.iinfo(np.int32).max
    vals = np.array([1, 5, 9, maxv - 1, maxv], np.int32)  # sorted, hits max
    lo = np.array([0, 0, 3], np.int64)
    hi = np.array([5, 5, 5], np.int64)
    targets = np.array([maxv, maxv - 1, maxv], np.int32)
    # small tiles force padding on both axes (5 % 4 != 0, 3 % 8 != 0)
    _check_run_probe(vals, lo, hi, targets, r_tile=8, v_tile=4)


def test_run_probe_tile_sizes_equivalent(rng):
    """Tile sizes are pure tiling parameters — results must not change."""
    n, r = 500, 100
    vals = np.sort(rng.integers(0, 1000, n)).astype(np.int64)
    lo = rng.integers(0, n + 1, r)
    hi = np.minimum(n, lo + rng.integers(0, 200, r))
    targets = rng.integers(0, 1000, r).astype(np.int64)
    outs = [run_probe_pallas(jnp.asarray(vals), jnp.asarray(lo),
                             jnp.asarray(hi), jnp.asarray(targets),
                             r_tile=rt, v_tile=vt, interpret=True)
            for rt, vt in [(32, 64), (128, 256), (256, 2048)]]
    for p, c in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0][0]), np.asarray(p))
        np.testing.assert_array_equal(np.asarray(outs[0][1]), np.asarray(c))


@given(st.data())
@settings(max_examples=20, deadline=None)
def test_run_probe_property(data):
    n = data.draw(st.integers(1, 200))
    r = data.draw(st.integers(1, 80))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    vals = np.sort(rng.integers(0, 100, n)).astype(np.int64)
    lo = rng.integers(0, n + 1, r)
    hi = np.minimum(n, lo + rng.integers(0, n + 1, r))
    targets = rng.integers(-10, 110, r).astype(np.int64)
    _check_run_probe(vals, lo, hi, targets, r_tile=32, v_tile=64)


# ------------------------------------------------- run_probe (prefetch grid)

def _check_run_probe_variants(vals, lo, hi, targets, **tiles):
    """Three-way pin: numpy truth, dense kernel, scalar-prefetch kernel."""
    args = (jnp.asarray(vals), jnp.asarray(lo), jnp.asarray(hi),
            jnp.asarray(targets))
    want_p, want_c = _run_probe_truth(vals, lo, hi, targets)
    for fn in (run_probe_pallas, run_probe_prefetch_pallas):
        p, c = fn(*args, interpret=True, **tiles)
        np.testing.assert_array_equal(np.asarray(p), want_p,
                                      err_msg=fn.__name__)
        np.testing.assert_array_equal(np.asarray(c), want_c,
                                      err_msg=fn.__name__)


@pytest.mark.parametrize("n,r,dt", [
    (1000, 77, np.int32), (5000, 300, np.int64), (131, 513, np.int32),
    (2048, 256, np.int64), (1, 1, np.int32), (10, 4096, np.int64),
])
def test_run_probe_prefetch_sweep(n, r, dt, rng):
    vals = np.sort(rng.integers(0, max(n * 3, 10), n)).astype(dt)
    lo = rng.integers(0, n + 1, r)
    hi = np.minimum(n, lo + rng.integers(0, n + 1, r))
    targets = rng.integers(-5, max(n * 3, 10) + 5, r).astype(dt)
    _check_run_probe_variants(vals, lo, hi, targets)


def test_run_probe_prefetch_window_shapes(rng):
    """The prefetch grid's block windows at their edge shapes: all-empty
    row blocks (zero value tiles streamed), a block whose runs sit inside
    one value tile, runs spanning tile boundaries, and a full-column run
    — each must agree with the dense kernel and the numpy truth."""
    n = 512
    vals = np.sort(rng.integers(0, 2000, n)).astype(np.int64)
    cases = []
    # every run empty: the prefetch kernel streams nothing and must still
    # initialise pos = lo, contains = False
    lo = rng.integers(0, n + 1, 64)
    cases.append((lo, lo.copy()))
    # all runs inside one value tile (v_tile=64 below): window = 1 tile
    lo = rng.integers(128, 160, 64)
    cases.append((lo, np.minimum(192, lo + rng.integers(0, 30, 64))))
    # runs straddling tile boundaries + a mixed batch with empties
    lo = np.asarray([0, 60, 63, 64, 120, 200, 200, 511] * 8)
    hi = np.minimum(n, lo + np.asarray([5, 10, 2, 65, 200, 0, 312, 1] * 8))
    cases.append((lo, hi))
    # one full-column run per block
    cases.append((np.zeros(64, np.int64), np.full(64, n, np.int64)))
    for lo, hi in cases:
        targets = rng.integers(-5, 2005, lo.shape[0]).astype(np.int64)
        _check_run_probe_variants(vals, lo, hi, targets, r_tile=32,
                                  v_tile=64)


def test_run_probe_prefetch_tile_sizes_equivalent(rng):
    """Tile sizes only reshape the prefetch grid — results must not move."""
    n, r = 500, 100
    vals = np.sort(rng.integers(0, 1000, n)).astype(np.int64)
    lo = rng.integers(0, n + 1, r)
    hi = np.minimum(n, lo + rng.integers(0, 200, r))
    targets = rng.integers(0, 1000, r).astype(np.int64)
    outs = [run_probe_prefetch_pallas(jnp.asarray(vals), jnp.asarray(lo),
                                      jnp.asarray(hi), jnp.asarray(targets),
                                      r_tile=rt, v_tile=vt, interpret=True)
            for rt, vt in [(32, 64), (128, 256), (256, 2048)]]
    for p, c in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0][0]), np.asarray(p))
        np.testing.assert_array_equal(np.asarray(outs[0][1]), np.asarray(c))


@pytest.mark.parametrize("tiles", [dict(r_tile=32, v_tile=64),
                                   dict(r_tile=256, v_tile=2048)])
def test_run_probe_mixed_dtype_promotion(tiles, rng):
    """int32 values probed with int64 targets (and vice versa) at
    non-tile-multiple shapes: both kernels must promote before padding —
    a +max pad in the narrow dtype would be a real value under the wide
    compare — and stay three-way parity-pinned at both tile sizes."""
    n, r = 333, 101  # neither a multiple of any tile size used
    for vdt, tdt in [(np.int32, np.int64), (np.int64, np.int32)]:
        vals = np.sort(rng.integers(0, 1000, n)).astype(vdt)
        lo = rng.integers(0, n + 1, r)
        hi = np.minimum(n, lo + rng.integers(0, 150, r))
        targets = rng.integers(-5, 1005, r).astype(tdt)
        # include the narrow dtype's max as a live target: under int64
        # promotion it must NOT match the int32 +max padding
        targets[0] = np.iinfo(np.int32).max
        _check_run_probe_variants(vals, lo, hi, targets, **tiles)


# ---------------------------------------------------------------- owned probe

@pytest.mark.parametrize("n_shards", [1, 2, 3, 7, 8, 64, 4095, MAX_SHARDS])
def test_shard_of_limbs_bit_exact(n_shards, rng):
    """The kernel-side 32-bit-limb splitmix64 shard hash must be bit-exact
    vs the uint64 reference for every shard count the kernel accepts —
    including extreme ids (0, int64 max) where limb carries matter."""
    subjects = np.concatenate([
        rng.integers(0, 1 << 62, 500),
        np.array([0, 1, 2**31 - 1, 2**31, 2**32 - 1, 2**32,
                  (1 << 62) - 1, np.iinfo(np.int64).max])]).astype(np.int64)
    u = subjects.astype(np.uint64)
    s_lo = jnp.asarray((u & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    s_hi = jnp.asarray((u >> np.uint64(32)).astype(np.uint32))
    got = np.asarray(shard_of_limbs(s_lo, s_hi, n_shards))
    want = np.asarray(ref.subject_shard_ref(jnp.asarray(subjects), n_shards))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n_shards,my_shard", [(1, 0), (2, 1), (4, 0),
                                               (4, 3), (8, 5)])
def test_eqrange_owned_pallas_matches_masking_path(n_shards, my_shard, rng):
    """The in-kernel ownership test vs the jnp mask-around-the-probe path:
    identical (lo, hi, owned) — non-owned rows degenerate to the empty
    run [lo, lo) inside the kernel."""
    n, q = 800, 257  # non-tile-multiple query count
    keys = np.sort(rng.integers(0, 3000, n)).astype(np.int64)
    queries = rng.integers(-5, 3005, q).astype(np.int64)
    subjects = rng.integers(-2**31, 2**31, q).astype(np.int32)
    lo_p, hi_p, own_p = eqrange_owned_pallas(
        jnp.asarray(keys), jnp.asarray(queries), jnp.asarray(subjects),
        my_shard, n_shards, interpret=True)
    owned = np.asarray(ref.subject_shard_ref(jnp.asarray(subjects),
                                             n_shards)) == my_shard
    want_lo = np.searchsorted(keys, queries, "left")
    want_hi = np.where(owned, np.searchsorted(keys, queries, "right"),
                       want_lo)
    np.testing.assert_array_equal(np.asarray(lo_p), want_lo)
    np.testing.assert_array_equal(np.asarray(hi_p), want_hi)
    np.testing.assert_array_equal(np.asarray(own_p), owned)


def test_eqrange_owned_dispatch_parity(rng):
    """kops.eqrange_owned on both FORCE settings returns identical bytes
    (the seam the owner-masking distributed config rides)."""
    from repro.kernels import ops as kops

    n, q = 500, 128
    keys = np.sort(rng.integers(0, 2000, n)).astype(np.int64)
    queries = rng.integers(0, 2000, q).astype(np.int64)
    subjects = rng.integers(-2**31, 2**31, q).astype(np.int32)
    outs = {}
    old = kops.FORCE
    try:
        for force in ("ref", "pallas"):
            kops.FORCE = force
            outs[force] = [np.asarray(x) for x in kops.eqrange_owned(
                jnp.asarray(keys), jnp.asarray(queries),
                jnp.asarray(subjects), jnp.int32(2), 4)]
    finally:
        kops.FORCE = old
    for a, b in zip(outs["ref"], outs["pallas"]):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------- fingerprint

@pytest.mark.parametrize("n,cols,cap", [
    (0, 0, 4), (0, 3, 8), (1, 1, 1), (5, 2, 16),
    (100, 4, 256), (513, 3, 1024), (2048, 6, 2048),
])
def test_fingerprint_three_way_parity(n, cols, cap, rng):
    """The digest contract: jnp oracle on a cap-sized masked table, Pallas
    kernel on the same, and the numpy host twin on the bare valid prefix
    must all be bit-identical — invalid-region garbage must not leak in.
    This is what lets the scheduler mix device-digested and host-replayed
    wave state under one cache key space."""
    from repro.kernels.fingerprint import fingerprint_rows_pallas

    full = rng.integers(-1000, 1000, (cap, cols)).astype(np.int32)
    prefix = full[:n].copy()
    valid = np.zeros((cap,), bool)
    valid[:n] = True
    want = ref.fingerprint_prefix_np(prefix)
    got_ref = np.asarray(ref.fingerprint_rows_ref(jnp.asarray(full),
                                                  jnp.asarray(valid)))
    assert tuple(int(x) for x in got_ref) == want
    if cols > 0:
        got_pal = np.asarray(fingerprint_rows_pallas(
            jnp.asarray(full), jnp.asarray(valid), r_tile=256,
            interpret=True))
        assert tuple(int(x) for x in got_pal) == want
    # garbage beyond the valid prefix must be invisible
    full2 = full.copy()
    full2[n:] = -7
    got2 = np.asarray(ref.fingerprint_rows_ref(jnp.asarray(full2),
                                               jnp.asarray(valid)))
    assert tuple(int(x) for x in got2) == want


def test_fingerprint_sensitivity():
    """Value, order and length perturbations all change the digest (the
    properties the digest-form cache key relies on)."""
    base = ref.fingerprint_prefix_np(np.array([[1, 2], [3, 4]], np.int32))
    assert base != ref.fingerprint_prefix_np(
        np.array([[3, 4], [1, 2]], np.int32))
    assert base != ref.fingerprint_prefix_np(
        np.array([[1, 2], [3, 5]], np.int32))
    assert base != ref.fingerprint_prefix_np(np.array([[1, 2]], np.int32))
    assert base != ref.fingerprint_prefix_np(
        np.array([[1, 2], [3, 4], [3, 4]], np.int32))


def test_fingerprint_dispatch_vmap():
    """kops.fingerprint_rows under vmap (the scheduler's whole-wave digest
    call) matches per-lane host digests on both FORCE settings."""
    import jax

    from repro.kernels import ops as kops

    rng = np.random.default_rng(3)
    rows = rng.integers(-1, 50, (4, 32, 3)).astype(np.int32)
    valid = np.zeros((4, 32), bool)
    lens = [0, 1, 7, 32]
    for j, m in enumerate(lens):
        valid[j, :m] = True
    want = [ref.fingerprint_prefix_np(rows[j, :m]) for j, m in enumerate(lens)]
    old = kops.FORCE
    try:
        for force in ("ref", "pallas"):
            kops.FORCE = force
            got = np.asarray(jax.vmap(kops.fingerprint_rows)(
                jnp.asarray(rows), jnp.asarray(valid)))
            assert [tuple(int(x) for x in g) for g in got] == want, force
    finally:
        kops.FORCE = old


# --------------------------------------------------------------- replay delta

@pytest.mark.parametrize("n_in,n_out,cap,n_vars,n_w,m_pad", [
    (1, 0, 8, 1, 0, 1),       # negative fragment: empty delta
    (1, 3, 16, 2, 1, 4),      # fresh-seed expansion
    (5, 5, 32, 3, 0, 8),      # pure filter unit: no write cols
    (7, 19, 64, 4, 2, 32),    # fan-out with two written columns
    (100, 257, 512, 5, 3, 512),
])
def test_replay_delta_three_way_parity(n_in, n_out, cap, n_vars, n_w, m_pad,
                                       rng):
    """The device-replay contract: the jnp oracle, the Pallas kernel
    (interpret mode) and the numpy host twin (``fragcache.replay``) must
    reconstruct bit-identical valid prefixes from the same cached delta —
    including padded delta widths (the scheduler pow2-pads per wave) and
    UNBOUND-filled dead regions."""
    from repro.core.fragcache import FragmentEntry, replay
    from repro.kernels.replay import replay_delta_pallas

    write_cols = tuple(range(n_w))
    seed = np.full((cap, n_vars), -1, np.int32)
    seed[:n_in] = rng.integers(0, 1000, (n_in, n_vars)).astype(np.int32)
    src = rng.integers(0, n_in, n_out).astype(np.int32)
    written = rng.integers(0, 1000, (n_out, max(n_w, 0))).astype(np.int32)
    entry = FragmentEntry(src_row=src,
                          written=written if n_w else
                          np.zeros((n_out, 0), np.int32),
                          overflow=False, ops=0)

    want_rows, want_valid = replay(entry, seed[:n_in], cap, n_vars,
                                   write_cols)

    # pad the delta like the scheduler does (pow2 wave width)
    src_p = np.zeros((m_pad,), np.int32)
    src_p[:n_out] = src
    wr_p = np.zeros((m_pad, n_w), np.int32)
    if n_w:
        wr_p[:n_out] = written

    got_ref = ref.replay_delta_ref(jnp.asarray(seed), jnp.asarray(src_p),
                                   jnp.asarray(wr_p), jnp.int32(n_out),
                                   write_cols)
    np.testing.assert_array_equal(np.asarray(got_ref[0]), want_rows)
    np.testing.assert_array_equal(np.asarray(got_ref[1]), want_valid)

    got_pal = replay_delta_pallas(jnp.asarray(seed), jnp.asarray(src_p),
                                  jnp.asarray(wr_p), jnp.int32(n_out),
                                  write_cols=write_cols, j_tile=16,
                                  i_tile=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_pal[0]), want_rows)
    np.testing.assert_array_equal(np.asarray(got_pal[1]), want_valid)


def test_replay_delta_dispatch_vmap():
    """kops.replay_delta under vmap (the scheduler's whole-wave replay
    call) matches the host twin on both FORCE settings."""
    import jax

    from repro.core.fragcache import FragmentEntry, replay
    from repro.kernels import ops as kops

    rng = np.random.default_rng(5)
    b, cap, n_vars = 3, 24, 3
    write_cols = (1,)
    rows = np.full((b, cap, n_vars), -1, np.int32)
    n_ins = [2, 5, 1]
    n_outs = [4, 0, 3]
    m = 4
    src = np.zeros((b, m), np.int32)
    wr = np.zeros((b, m, 1), np.int32)
    want = []
    for j in range(b):
        rows[j, :n_ins[j]] = rng.integers(0, 99, (n_ins[j], n_vars))
        src[j, :n_outs[j]] = rng.integers(0, n_ins[j], n_outs[j])
        wr[j, :n_outs[j], 0] = rng.integers(0, 99, n_outs[j])
        entry = FragmentEntry(
            src_row=src[j, :n_outs[j]].copy(),
            written=wr[j, :n_outs[j]].copy(), overflow=False, ops=0)
        want.append(replay(entry, rows[j, :n_ins[j]], cap, n_vars,
                           write_cols))
    old = kops.FORCE
    try:
        for force in ("ref", "pallas"):
            kops.FORCE = force
            r_o, v_o = jax.vmap(
                lambda r, s, w, n: kops.replay_delta(r, s, w, n, write_cols)
            )(jnp.asarray(rows), jnp.asarray(src), jnp.asarray(wr),
              jnp.asarray(np.asarray(n_outs, np.int32)))
            for j in range(b):
                np.testing.assert_array_equal(np.asarray(r_o[j]), want[j][0],
                                              err_msg=f"{force} lane {j}")
                np.testing.assert_array_equal(np.asarray(v_o[j]), want[j][1])
    finally:
        kops.FORCE = old


# ------------------------------------------------------- segment run lengths

def test_max_run_length_per_segment_matches_bruteforce(rng):
    keys = np.sort(rng.integers(0, 40, 500)).astype(np.int64)
    seg_of = keys // 10  # 4 segments; runs never cross boundaries
    want = np.zeros((6,), np.int64)
    for seg in range(6):
        ks = keys[seg_of == seg]
        if ks.size:
            want[seg] = np.bincount(ks - ks.min()).max()
    got = np.asarray(ref.max_run_length_per_segment_ref(
        jnp.asarray(keys), jnp.asarray(seg_of), 6))
    np.testing.assert_array_equal(got, want)
    # empty input
    got0 = np.asarray(ref.max_run_length_per_segment_ref(
        jnp.asarray(np.zeros((0,), np.int64)),
        jnp.asarray(np.zeros((0,), np.int64)), 3))
    np.testing.assert_array_equal(got0, np.zeros((3,), np.int64))


# ------------------------------------------------------------ flash_attention

@pytest.mark.parametrize("shape,causal,dt", [
    ((1, 2, 2, 128, 128, 64), True, jnp.float32),
    ((2, 4, 2, 256, 256, 64), True, jnp.float32),  # GQA group=2
    ((1, 8, 1, 100, 100, 32), False, jnp.float32),  # MQA, ragged seq
    ((1, 2, 1, 64, 192, 128), False, jnp.bfloat16),  # cross len + bf16
    ((1, 4, 4, 1, 300, 64), False, jnp.float32),  # decode shape
    ((1, 2, 2, 33, 65, 16), True, jnp.float32),  # non-aligned everything
])
def test_flash_attention_sweep(shape, causal, dt, rng):
    b, hq, hkv, sq, sk, d = shape
    q = jnp.asarray(rng.normal(size=(b, hq, sq, d)), dt)
    k = jnp.asarray(rng.normal(size=(b, hkv, sk, d)), dt)
    v = jnp.asarray(rng.normal(size=(b, hkv, sk, d)), dt)
    o1 = flash_attention_pallas(q, k, v, causal=causal, block_q=64,
                                block_k=64, interpret=True)
    o2 = ref.attention_ref(q, k, v, causal=causal)
    err = np.max(np.abs(np.asarray(o1, np.float32)
                        - np.asarray(o2, np.float32)))
    tol = 3e-2 if dt == jnp.bfloat16 else 2e-5
    assert err < tol, err


def test_flash_attention_matches_block_sizes(rng):
    """Block size must not change the result (pure tiling parameter)."""
    q = jnp.asarray(rng.normal(size=(1, 4, 130, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 130, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 130, 64)), jnp.float32)
    outs = [flash_attention_pallas(q, k, v, causal=True, block_q=bq,
                                   block_k=bk, interpret=True)
            for bq, bk in [(32, 32), (64, 128), (128, 64)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=2e-5, atol=2e-5)


def test_embedding_bag_modes(rng):
    table = jnp.asarray(rng.normal(size=(50, 8)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 50, (4, 6)), jnp.int32)
    s = ref.embedding_bag_ref(table, ids, "sum")
    m = ref.embedding_bag_ref(table, ids, "mean")
    np.testing.assert_allclose(np.asarray(s) / 6.0, np.asarray(m), rtol=1e-6)
    want = np.stack([np.asarray(table)[np.asarray(ids)[i]].sum(0)
                     for i in range(4)])
    np.testing.assert_allclose(np.asarray(s), want, rtol=1e-5)
