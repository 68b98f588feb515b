"""The load generator: one seed, one traffic; every instance answerable."""

import itertools
import json
from pathlib import Path

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


# ------------------------------------------------- the streams, pinned
DATA = Path(__file__).resolve().parent / "data"
PINNED = json.loads((DATA / "2stars-closed32.pinned.json").read_text())


@pytest.mark.parametrize("seed", sorted(PINNED["seeds"]))
def test_streams_match_the_pinned_ones(mix, seed):
    """The 2-stars mix's instances, warm-up and window, are those the
    one-link generator drew before templates became star chains, text
    for text, with the same counts."""
    want = PINNED["seeds"][seed]
    g = watdiv.generate(watdiv.GraphConfig(scale=PINNED["scale"]), int(seed))
    cap = load_cell("spf-2stars-cold").config["engine"]["cap"]
    insts = tf.Instances(g, mix["templates"], PINNED["fit_rows"], int(seed),
                         reserve=PINNED["reserve"], cap=cap)
    assert (insts.generated, insts.kept, insts.pool()) \
        == (want["generated"], want["kept"], want["pool"])
    assert [[i.text for i in pool] for pool in insts.warmup()] \
        == want["warmup"]
    assert [i.text for i in _window(insts, PINNED["window"])] \
        == want["window"]


def test_one_hop_bound_is_card_or_degree_sum(mix):
    """For a one-hop chain the bound is the larger of the anchor
    constant's entity count and the sum of those entities' link
    out-degrees, counted here entity by entity: on the 2-stars mix's
    functional links and on a link of out-degree 2 on average."""
    g = _graph(5)
    templates = mix["templates"] + FANOUT[:1]
    insts = tf.Instances(g, templates, 10**9, 5, reserve=0)
    for t, roots, bounds in zip(templates, insts.roots, insts.bounds):
        lo, _ = g.class_ranges[t.root_class]
        ptr = g.rel_ptr[t.hops[0].rel]
        for r, b in list(zip(roots, bounds))[:20]:
            want = []
            for p in t.const_branches():
                same = np.nonzero(g.attr[p] == g.attr[p][r - lo])[0]
                want.append((same.shape[0], max(
                    same.shape[0], int((ptr[same + 1] - ptr[same]).sum()))))
            assert b == min(want)[1]


# ------------------------------------------------------ the union load
@pytest.fixture(scope="module")
def union():
    return tf.load_mix(DATA / "union-test.json")


def test_union_mix_has_every_shape(union):
    shapes = [len(t.hops) for t in union["templates"]]
    assert shapes[:3] == [0, 1, 2] and 3 <= shapes[3] <= 9
    path = union["templates"][3]
    assert path.anchor == "subject" and not path.root
    assert all(not h.star for h in path.hops)


def _anchor_first(g, inst):
    """The instance's patterns with its anchor first: the root star's
    constant that matches the fewest triples (the first of equals), or,
    for a subject anchor, the patterns as they are (the first has the
    constant subject)."""
    pats = list(inst.patterns)
    consts = [i for i, (s, _, o) in enumerate(pats)
              if isinstance(s, tuple) and s[1] == 0
              and not isinstance(o, tuple)]
    if consts:
        card = [int(((g.p == pats[i][1]) & (g.o == pats[i][2])).sum())
                for i in consts]
        pats.insert(0, pats.pop(consts[int(np.argmin(card))]))
    return pats


def _table_sizes(ref, pats):
    """The binding table's rows after each pattern of a plan that joins
    ``pats`` in their order: the solutions of each prefix, whose
    variables are renumbered by first appearance."""
    sizes = []
    for k in range(1, len(pats) + 1):
        ids = {}
        prefix = [tuple(("v", ids.setdefault(x[1], len(ids)))
                        if isinstance(x, tuple) else x for x in (s, p, o))
                  for s, p, o in pats[:k]]
        sizes.append(ref.answers(prefix, len(ids)).shape[0])
    return sizes


@pytest.mark.parametrize("fit_rows", [8, 64, 1024])
def test_union_instances_answer_and_fit(union, fit_rows):
    """Every instance of each shape has an answer, and the reference's
    binding tables, joined from the anchor first, stay within the cut."""
    g = _graph(3)
    ref = Reference(g.s, g.p, g.o, g.n_terms)
    insts = tf.Instances(g, union["templates"], fit_rows, 3, reserve=4,
                         cap=64)
    sent = _window(insts, 40) + [i for p in insts.warmup() for i in p]
    assert {i.template for i in sent} == set(range(4))
    for inst in sent:
        rows = ref.answers(inst.patterns, inst.n_vars)
        assert rows.shape[0] >= 1 and rows.shape[1] == inst.n_vars
        assert max(_table_sizes(ref, _anchor_first(g, inst))) <= fit_rows


def test_union_pool_runs_out_loudly(union):
    insts = tf.Instances(_graph(4), union["templates"], 4, 4, reserve=2)
    n = insts.pool()
    with pytest.raises(tf.PoolExhausted):
        _window(insts, n + 1)


@pytest.mark.parametrize("cap", [64, 1024])
def test_a_bound_past_cap_moves_into_the_warm_up(union, cap):
    """A template whose largest bound passes the engine's starting
    capacity gives that instance to ``past_cap()``, which the window never
    sends; the template's other instances keep their order, and every
    other template is as it is with no cap."""
    g = _graph(6)
    plain = tf.Instances(g, union["templates"], 10**6, 6, reserve=4)
    capped = tf.Instances(g, union["templates"], 10**6, 6, reserve=4,
                          cap=cap)
    window = set()
    with pytest.raises(tf.PoolExhausted):
        for inst in capped.stream():
            window.add(inst.text)
    big = {i.template: i for i in capped.past_cap()}
    for ti, roots in enumerate(plain.roots):
        if plain.bounds[ti].max() > cap:
            top = int(np.argmax(plain.bounds[ti]))
            assert np.array_equal(capped.roots[ti], np.delete(roots, top))
            assert big[ti].text == plain._instance(ti, roots[top]).text
            assert big[ti].text not in window
        else:
            assert ti not in big
            assert np.array_equal(capped.roots[ti], roots)
    assert 0 < len(big) < len(union["templates"])  # both cases are here
    assert capped.pool() == plain.pool() - len(big)


# chains with fan-out hops and stars between them, beyond the union mix:
# User -28-> Product (out-degree 2 on average), User -27-> Review -30->
# Product (1.5, then 1), a subject-anchored chain with every kind of
# star, and a Product constant whose common values match more products
# than the retailers' attribute has triples
FANOUT = [tf.Template.from_json(t) for t in (
    {"root_class": 0, "root": [[0, True], [2, False]],
     "hops": [{"rel": 28, "star": [5, 6]}]},
    {"root_class": 0, "root": [[1, True]],
     "hops": [{"rel": 27, "star": [14]}, {"rel": 30, "star": [7]}]},
    {"root_class": 0, "root": [[0, False]], "anchor": "subject",
     "hops": [{"rel": 27, "star": [14, 15]}, {"rel": 31, "star": [1]},
              {"rel": 28, "star": [5]}]},
    {"root_class": 1, "root": [[9, True]],
     "hops": [{"rel": 29, "star": [18]}]},
)]


def _with_bounds(insts, n):
    """The window's first ``n`` instances, each with its table bound."""
    used = [0] * len(insts.templates)
    out = []
    for inst in _window(insts, n):
        out.append((inst, insts.bounds[inst.template][used[inst.template]]))
        used[inst.template] += 1
    return out


@pytest.mark.parametrize("which", ["union", "fanout"])
def test_bound_covers_every_table(union, which):
    """The reference's tables, joined from the anchor first, never pass
    an instance's bound; from a subject anchor the bound is exactly the
    largest of them (every star after it keeps or drops whole rows).  The
    anchor matches fewer triples than any other pattern's predicate."""
    templates = union["templates"] if which == "union" else FANOUT
    g = _graph(8)
    ref = Reference(g.s, g.p, g.o, g.n_terms)
    insts = tf.Instances(g, templates, 10**6, 8, reserve=0)
    count = np.bincount(g.p, minlength=g.n_predicates)
    past_card = 0
    for inst, bound in _with_bounds(insts, 30 * len(templates)):
        pats = _anchor_first(g, inst)
        sizes = _table_sizes(ref, pats)
        assert max(sizes) <= bound
        assert sizes[0] < min(count[p] for _, p, _ in pats[1:])
        if templates[inst.template].anchor == "subject":
            assert max(sizes) == bound
        past_card += max(sizes) > sizes[0]
    if which == "fanout":
        assert past_card  # the hops did multiply rows here
        assert insts.kept[3] < insts.generated[3]  # and the check cut


@pytest.mark.parametrize("entry", [
    {"root_class": 0, "root": [[0, False]]},  # no anchor at all
    {"root_class": 0, "root": [[0, True]], "anchor": "subject"},  # both
    {"root_class": 0, "root": [[0, True]], "anchor": "object"},
])
def test_template_has_one_anchor(entry):
    with pytest.raises(ValueError):
        tf.Template.from_json(entry)
