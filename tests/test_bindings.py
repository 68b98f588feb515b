"""Tests for the static-shape join primitives.

The probe/membership primitives live in the backend-dispatched kernel
layer (``repro.kernels.ops``); the table machinery (``expand``) stays in
``repro.core.bindings``.  Deterministic cases run everywhere; the
property tests additionally run when hypothesis is installed.
"""

import jax.numpy as jnp
import numpy as np

from _hyp import given, settings, st

import pytest

from repro.core.bindings import BindingTable, compact, expand
from repro.kernels.ops import eqrange, run_contains, searchsorted_in_runs


# --------------------------------------------------------------------------
# deterministic cases (always run, even without hypothesis)
# --------------------------------------------------------------------------

def test_eqrange_basic():
    keys = jnp.asarray(np.array([1, 3, 3, 3, 7, 9], np.int64))
    q = jnp.asarray(np.array([0, 1, 3, 5, 9, 10], np.int64))
    lo, hi = eqrange(keys, q)
    np.testing.assert_array_equal(np.asarray(lo), [0, 0, 1, 4, 5, 6])
    np.testing.assert_array_equal(np.asarray(hi), [0, 1, 4, 4, 6, 6])


def test_run_contains_basic():
    vals = jnp.asarray(np.array([1, 2, 4, 4, 6, 9, 0, 5], np.int32))
    lo = jnp.asarray(np.array([0, 0, 2, 5, 6, 3], np.int32))
    hi = jnp.asarray(np.array([6, 6, 5, 5, 8, 3], np.int32))
    t = jnp.asarray(np.array([4, 3, 6, 9, 5, 1], np.int32))
    got = np.asarray(run_contains(vals, lo, hi, t))
    #           4 in run, 3 not, 6 in [2:5)? vals[2:5]=[4,4,6] yes,
    #           empty [5:5), 5 in [6:8)=[0,5] yes, empty [3:3)
    np.testing.assert_array_equal(got, [True, False, True, False, True, False])


def test_searchsorted_in_runs_basic():
    vals = jnp.asarray(np.array([1, 2, 4, 4, 6, 9], np.int32))
    lo = jnp.asarray(np.array([0, 2, 0, 4], np.int32))
    hi = jnp.asarray(np.array([6, 5, 0, 6], np.int32))
    t = jnp.asarray(np.array([4, 5, 3, 10], np.int32))
    got = np.asarray(searchsorted_in_runs(vals, lo, hi, t))
    want = [l + np.searchsorted(np.asarray(vals)[l:h], tv, "left")
            for l, h, tv in zip(np.asarray(lo), np.asarray(hi), np.asarray(t))]
    np.testing.assert_array_equal(got, want)


def test_expand_basic():
    lo = jnp.asarray(np.array([0, 4, 10], np.int64))
    hi = jnp.asarray(np.array([2, 4, 13], np.int64))
    valid = jnp.asarray(np.array([True, True, True]))
    ex = expand(lo, hi, valid, cap=8)
    assert int(ex.total) == 5
    np.testing.assert_array_equal(np.asarray(ex.src_row)[:5], [0, 0, 2, 2, 2])
    np.testing.assert_array_equal(np.asarray(ex.flat_idx)[:5],
                                  [0, 1, 10, 11, 12])
    assert int(np.asarray(ex.valid).sum()) == 5


def test_expand_invalid_rows_contribute_nothing():
    lo = jnp.asarray(np.array([0, 4], np.int64))
    hi = jnp.asarray(np.array([3, 6], np.int64))
    valid = jnp.asarray(np.array([False, True]))
    ex = expand(lo, hi, valid, cap=4)
    assert int(ex.total) == 2
    np.testing.assert_array_equal(np.asarray(ex.src_row)[:2], [1, 1])
    np.testing.assert_array_equal(np.asarray(ex.flat_idx)[:2], [4, 5])


def test_expand_overflow_clamps_to_cap():
    lo = jnp.asarray(np.array([0], np.int64))
    hi = jnp.asarray(np.array([10], np.int64))
    valid = jnp.asarray(np.array([True]))
    ex = expand(lo, hi, valid, cap=4)
    assert int(ex.total) == 10  # true total, unclamped
    assert int(np.asarray(ex.valid).sum()) == 4  # output rows clamp to cap


def test_expand_searchsorted_backend_parity():
    """expand's cumulative-degree search routes through the kernel layer
    (kops.searchsorted); both backends must produce byte-identical
    expansions (ROADMAP follow-up from the dispatch-layer refactor)."""
    from repro.kernels import ops as kops

    rng = np.random.default_rng(7)
    lo64 = rng.integers(0, 50, 40)
    hi64 = lo64 + rng.integers(0, 6, 40)
    valid_np = rng.random(40) > 0.3
    lo = jnp.asarray(lo64)
    hi = jnp.asarray(hi64)
    valid = jnp.asarray(valid_np)
    out = {}
    old = kops.FORCE
    try:
        for force in ["ref", "pallas"]:
            kops.FORCE = force
            ex = expand(lo, hi, valid, cap=128)
            out[force] = tuple(np.asarray(x).tobytes() for x in ex)
    finally:
        kops.FORCE = old
    assert out["ref"] == out["pallas"]


# --------------------------------------------------------------------------
# property tests (hypothesis)
# --------------------------------------------------------------------------

@given(st.lists(st.integers(0, 100), min_size=1, max_size=100),
       st.lists(st.integers(-5, 105), min_size=1, max_size=50))
@settings(max_examples=50, deadline=None)
def test_eqrange_matches_numpy(keys, queries):
    keys = np.sort(np.array(keys, np.int64))
    q = np.array(queries, np.int64)
    lo, hi = eqrange(jnp.asarray(keys), jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(lo),
                                  np.searchsorted(keys, q, "left"))
    np.testing.assert_array_equal(np.asarray(hi),
                                  np.searchsorted(keys, q, "right"))


@given(st.data())
@settings(max_examples=50, deadline=None)
def test_searchsorted_in_runs(data):
    n = data.draw(st.integers(4, 120))
    vals = np.sort(np.array(data.draw(
        st.lists(st.integers(0, 50), min_size=n, max_size=n)), np.int32))
    n_rows = data.draw(st.integers(1, 20))
    lo = np.array([data.draw(st.integers(0, n)) for _ in range(n_rows)])
    hi = np.array([min(n, l + data.draw(st.integers(0, n)))
                   for l in lo])
    hi = np.maximum(hi, lo)
    targets = np.array([data.draw(st.integers(-2, 52))
                        for _ in range(n_rows)], np.int32)
    got = np.asarray(searchsorted_in_runs(
        jnp.asarray(vals), jnp.asarray(lo), jnp.asarray(hi),
        jnp.asarray(targets)))
    want = np.array([l + np.searchsorted(vals[l:h], t, "left")
                     for l, h, t in zip(lo, hi, targets)])
    np.testing.assert_array_equal(got, want)
    # membership agrees with python `in`
    got_c = np.asarray(run_contains(
        jnp.asarray(vals), jnp.asarray(lo), jnp.asarray(hi),
        jnp.asarray(targets)))
    want_c = np.array([t in vals[l:h].tolist()
                       for l, h, t in zip(lo, hi, targets)])
    np.testing.assert_array_equal(got_c, want_c)


@given(st.data())
@settings(max_examples=50, deadline=None)
def test_expand_enumerates_runs(data):
    n_rows = data.draw(st.integers(1, 16))
    lo = np.array([data.draw(st.integers(0, 30)) for _ in range(n_rows)])
    deg = np.array([data.draw(st.integers(0, 6)) for _ in range(n_rows)])
    hi = lo + deg
    valid = np.array([data.draw(st.booleans()) for _ in range(n_rows)])
    cap = data.draw(st.integers(1, 64))
    ex = expand(jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(valid), cap)
    want = [(r, lo[r] + j) for r in range(n_rows) if valid[r]
            for j in range(deg[r])]
    total = len(want)
    assert int(ex.total) == total
    got = [(int(ex.src_row[i]), int(ex.flat_idx[i]))
           for i in range(min(cap, total))]
    assert got == want[:cap]
    assert np.asarray(ex.valid).sum() == min(cap, total)


@pytest.mark.parametrize("pattern", ["all", "none", "prefix", "suffix",
                                     "random"])
def test_compact_is_a_stable_partition(pattern):
    """``compact`` moves valid rows to a prefix in their original order,
    then the invalid rows in theirs — the permutation of a stable argsort
    on ``~valid``, built without a sort."""
    rng = np.random.default_rng(7)
    n = 37
    valid = {"all": np.ones(n, bool), "none": np.zeros(n, bool),
             "prefix": np.arange(n) < 11, "suffix": np.arange(n) >= 11,
             "random": rng.random(n) < 0.4}[pattern]
    rows = rng.integers(-1, 1000, (n, 3)).astype(np.int32)
    out = compact(BindingTable(jnp.asarray(rows), jnp.asarray(valid),
                               jnp.asarray(False)))
    order = np.argsort(~valid, kind="stable")
    np.testing.assert_array_equal(np.asarray(out.rows), rows[order])
    np.testing.assert_array_equal(np.asarray(out.valid), valid[order])

