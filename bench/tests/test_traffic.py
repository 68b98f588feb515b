"""The load generator: one seed, one traffic; every instance answerable."""

import itertools

import numpy as np
import pytest

import traffic as tf
import watdiv
from harness import load_cell
from reference import Reference

SCALE = 100


@pytest.fixture(scope="module")
def mix():
    return load_cell("spf-2stars-cold").mix


def _graph(seed):
    return watdiv.generate(watdiv.GraphConfig(scale=SCALE), seed)


def test_graph_shape_is_seed_independent():
    a, b = _graph(1), _graph(2**31 + 11)
    assert a.n_triples == b.n_triples
    for p in range(a.n_predicates):
        assert (a.p == p).sum() == (b.p == p).sum()
    assert not np.array_equal(a.o, b.o)
    assert np.unique(np.stack([a.s, a.p, a.o], 1), axis=0).shape[0] \
        == a.n_triples


def _window(insts, n):
    return list(itertools.islice(insts.stream(), n))


@pytest.mark.parametrize("seed", [7, 2**33 + 5])
def test_same_seed_same_instances(mix, seed):
    runs = []
    for _ in range(2):
        g = _graph(seed)
        insts = tf.Instances(g, mix["templates"], 64, seed, reserve=8)
        runs.append(([i.text for i in _window(insts, 40)],
                     [i.text for pool in insts.warmup() for i in pool]))
    (w1, u1), (w2, u2) = runs
    assert w1 == w2 and u1 == u2
    assert len(set(w1)) == len(w1)  # never repeated
    assert not set(w1) & set(u1)  # warm-up sends none of the window's
    k = len(mix["templates"])
    assert len(u1) == 8 * k
    # every block of k requests holds each template once
    tpl = [i.template for i in _window(insts, 40)]
    assert all(sorted(tpl[j:j + k]) == list(range(k))
               for j in range(0, 40 - k + 1, k))


def test_pool_runs_out_loudly(mix):
    insts = tf.Instances(_graph(4), mix["templates"], 4, 4, reserve=2)
    n = insts.pool()
    with pytest.raises(tf.PoolExhausted):
        _window(insts, n + 1)


def test_every_instance_answers_and_fits(mix):
    g = _graph(3)
    ref = Reference(g.s, g.p, g.o, g.n_terms)
    insts = tf.Instances(g, mix["templates"], 64, 3, reserve=8)
    for inst in _window(insts, 40):
        rows = ref.answers(inst.patterns, inst.n_vars)
        assert 1 <= rows.shape[0] <= 64
        assert rows.shape[1] == inst.n_vars
        assert inst.text.startswith("SELECT * WHERE {")
