"""Triple-store invariants: index sort order, cardinalities, sharding,
and the int32 composite-key layout."""

import numpy as np
import pytest
from _hyp import given, settings, st

from repro.rdf.store import KEY_LIMIT, TripleStore, _subject_hash


@st.composite
def triple_sets(draw):
    n = draw(st.integers(1, 200))
    n_terms = draw(st.integers(5, 50))
    n_preds = draw(st.integers(1, 6))
    s = draw(st.lists(st.integers(0, n_terms - 1), min_size=n, max_size=n))
    p = draw(st.lists(st.integers(0, n_preds - 1), min_size=n, max_size=n))
    o = draw(st.lists(st.integers(0, n_terms - 1), min_size=n, max_size=n))
    return (np.array(s), np.array(p), np.array(o), n_terms, n_preds)


@given(triple_sets())
@settings(max_examples=25, deadline=None)
def test_indexes_sorted_and_consistent(data):
    s, p, o, n_terms, n_preds = data
    store = TripleStore.build(s, p, o, n_terms=n_terms, n_predicates=n_preds)
    assert np.all(np.diff(store.h_key_ps) >= 0)
    assert np.all(np.diff(store.h_key_po) >= 0)
    # dedup: n_triples equals distinct triple count
    uniq = len({(a, b, c) for a, b, c in zip(s, p, o)})
    assert store.n_triples == uniq
    # both orders contain the same multiset of triples
    p1 = store.h_key_ps // store.n_terms
    p2 = store.h_key_po // store.n_terms
    assert np.bincount(p1, minlength=n_preds).tolist() == \
        np.bincount(p2, minlength=n_preds).tolist()


@given(triple_sets())
@settings(max_examples=25, deadline=None)
def test_cardinality_matches_bruteforce(data):
    s, p, o, n_terms, n_preds = data
    store = TripleStore.build(s, p, o, n_terms=n_terms, n_predicates=n_preds)
    triples = {(a, b, c) for a, b, c in zip(s.tolist(), p.tolist(), o.tolist())}
    rng = np.random.default_rng(0)
    for _ in range(10):
        pp = int(rng.integers(0, n_preds))
        ss = int(rng.integers(0, n_terms))
        oo = int(rng.integers(0, n_terms))
        assert store.tp_cardinality(pp) == sum(t[1] == pp for t in triples)
        assert store.tp_cardinality(pp, s=ss) == sum(
            t[0] == ss and t[1] == pp for t in triples)
        assert store.tp_cardinality(pp, o=oo) == sum(
            t[1] == pp and t[2] == oo for t in triples)
        assert store.tp_cardinality(pp, s=ss, o=oo) == int(
            (ss, pp, oo) in triples)


@given(triple_sets(), st.integers(2, 8))
@settings(max_examples=15, deadline=None)
def test_subject_sharding_partitions(data, n_shards):
    s, p, o, n_terms, n_preds = data
    store = TripleStore.build(s, p, o, n_terms=n_terms, n_predicates=n_preds)
    shards = store.shard_by_subject(n_shards)
    # every real triple lands on exactly the shard its subject hashes to
    total_real = 0
    for i, sh in enumerate(shards):
        pred = sh.h_key_ps // sh.n_terms
        real = pred < n_preds  # padding uses predicate id n_preds
        subs = sh.h_s_pso[real].astype(np.int64)
        assert np.all(_subject_hash(subs) % n_shards == i)
        total_real += int(real.sum())
    assert total_real == store.n_triples
    # shards are equal-length (padded)
    lens = {sh.n_triples for sh in shards}
    assert len(lens) == 1


# ------------------------------------------------- int32 composite keys

def _near_bound_store(rng, n_preds=3, n=400):
    """A store whose radix product sits just under the int32 key limit,
    with term ids crowding the top of the dictionary (keys near 2**31)."""
    n_terms = (KEY_LIMIT - 1) // (n_preds + 1)
    top = n_terms - 64
    s = rng.integers(top, n_terms, n)
    p = rng.integers(0, n_preds, n)
    o = rng.integers(top, n_terms, n)
    return TripleStore.build(s, p, o, n_terms=n_terms, n_predicates=n_preds)


def test_composite_keys_are_int32():
    """Host and device key columns — base, delta, post-compaction — are
    int32: the probe kernels take no 64-bit operands on the chip."""
    rng = np.random.default_rng(0)
    store = _near_bound_store(rng)
    names = ("key_ps_pso", "key_po_pos", "ins_key_ps", "ins_key_po")
    assert store.h_key_ps.dtype == store.h_key_po.dtype == np.int32
    assert all(getattr(store.device, f).dtype == np.int32 for f in names)
    ms, mp, mo = store.merged_triples()
    store.apply_delta(insert=([store.n_terms - 1], [0], [store.n_terms - 1]),
                      delete=(ms[:3], mp[:3], mo[:3]))
    assert store.h_ins_key_ps.dtype == store.h_ins_key_po.dtype == np.int32
    assert all(getattr(store.device, f).dtype == np.int32 for f in names)
    assert int(store.h_ins_key_ps.max()) < KEY_LIMIT
    store.compact()
    assert store.h_key_ps.dtype == store.h_key_po.dtype == np.int32


@pytest.mark.parametrize("n_terms,n_preds,fits", [
    ((KEY_LIMIT - 1) // 4, 3, True),   # largest dictionary for 3 predicates
    ((KEY_LIMIT - 1) // 4 + 1, 3, False),
    (2**31, 0, False),
    (1_488_500, 33, True),             # WatDiv at ~10M triples
    (50_000_000, 64, False),
])
def test_radix_check_int32_bound(n_terms, n_preds, fits):
    """``build`` refuses a dictionary whose keys would not fit int32 —
    counting the shard padding predicate ``n_predicates`` — with a
    message naming the bound."""
    s = p = o = np.zeros(1, np.int64)
    if fits:
        store = TripleStore.build(s, p, o, n_terms=n_terms,
                                  n_predicates=n_preds)
        assert store.h_key_ps.dtype == np.int32
        return
    with pytest.raises(ValueError, match="int32"):
        TripleStore.build(s, p, o, n_terms=n_terms, n_predicates=n_preds)


@pytest.mark.parametrize("force", ["ref", "pallas"])
def test_near_int32_bound_parity_through_delta_and_compaction(force):
    """Keys just under 2**31 stay exact through the engine: results match
    the brute-force oracle on the base, with a delta overlaid, and after
    compaction — on the jnp oracles and on the Pallas kernels."""
    from repro.core import EngineConfig, QueryEngine, results_as_numpy
    from repro.core.oracle import eval_bgp_bruteforce, table_to_solution_set
    from repro.core.patterns import BGP, C, TriplePattern, V
    from repro.kernels import ops

    rng = np.random.default_rng(1)
    store = _near_bound_store(rng)
    top_o = int(store.h_o_pso[-1])
    queries = [
        BGP((TriplePattern(V(0), C(1), V(1)),), n_vars=2),
        BGP((TriplePattern(V(0), C(2), C(top_o)),), n_vars=1),
        BGP((TriplePattern(V(0), C(0), V(1)),
             TriplePattern(V(0), C(2), V(2))), n_vars=3),
    ]

    def check():
        s, p, o = store.merged_triples()
        eng = QueryEngine(store, EngineConfig(interface="spf", cap=512))
        for q in queries:
            table, _ = eng.run(q)
            assert table_to_solution_set(results_as_numpy(table)) \
                == eval_bgp_bruteforce(s, p, o, q)

    old = ops.FORCE
    ops.FORCE = force
    try:
        check()
        ms, mp, mo = store.merged_triples()
        idx = rng.choice(ms.shape[0], 30, replace=False)
        top = store.n_terms - 64
        store.apply_delta(
            insert=(rng.integers(top, store.n_terms, 20),
                    rng.integers(0, 3, 20),
                    rng.integers(top, store.n_terms, 20)),
            delete=(ms[idx], mp[idx], mo[idx]))
        check()
        store.compact()
        check()
    finally:
        ops.FORCE = old
