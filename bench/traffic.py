"""The load generator: query instances and their send times, from a seed.

A traffic mix is a data file under ``traffic/``; this module is the one
general generator that reads it.  Its parts:

- **templates** (in the mix file): star-chain query shapes over the
  WatDiv schema.  A template names its root class, the root star's
  branches (predicate, and whether the object is a constant), the link
  predicate and the linked star's branches.  The shapes were drawn with
  the WatDiv witness generator (``repro.rdf.queries._gen_star_query``,
  2-stars load) and frozen in the file, so no change to the program can
  move them.
- **instances**: a template with constants.  As the witness generator
  does, an instance is rooted at a real entity of the root class that
  has every predicate of the template, and reads its constants from that
  entity's own triples, so every instance has at least one answer.
- **the fit cut**: an instance is sent only if the benchmark's own host
  count bounds every binding table at the configuration's ``fit_rows``:
  the rows of the root star's most selective constant, and those rows
  times their link out-degrees; and only if that constant is the query's
  most selective pattern, so that a selective-first plan starts from it
  (see ``Instances``).
- **the pool**: the distinct instances that pass the cut.  A run never
  sends one twice; a run that would need more than the pool holds stops
  with ``PoolExhausted``, and the benchmark then prints no result.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from watdiv import Graph


@dataclass(frozen=True)
class Template:
    root_class: int
    root: tuple[tuple[int, bool], ...]  # (predicate, object is constant)
    link: int  # relation predicate from the root to the linked star
    leaf: tuple[int, ...]  # the linked star's attribute predicates

    @staticmethod
    def from_json(t: dict) -> "Template":
        return Template(int(t["root_class"]),
                        tuple((int(p), bool(c)) for p, c in t["root"]),
                        int(t["link"]), tuple(int(p) for p in t["leaf"]))


@dataclass(frozen=True)
class Instance:
    template: int  # index into the mix's templates
    text: str  # SPARQL the client sends
    patterns: tuple[tuple[object, int, object], ...]  # ('v', i) or const id
    n_vars: int


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    mix["templates"] = [Template.from_json(t) for t in mix["templates"]]
    return mix


def instance_patterns(t: Template, consts: dict[int, int]):
    """Triple patterns of template ``t`` with root constants ``consts``
    (predicate -> object id).  Variables are numbered by first
    appearance, which is how the endpoint's parser numbers them."""
    pats, nxt = [], 1
    for p, is_const in t.root:
        if is_const:
            pats.append((("v", 0), p, consts[p]))
        else:
            pats.append((("v", 0), p, ("v", nxt)))
            nxt += 1
    y = nxt
    pats.append((("v", 0), t.link, ("v", y)))
    nxt += 1
    for p in t.leaf:
        pats.append((("v", y), p, ("v", nxt)))
        nxt += 1
    return tuple(pats), nxt


def to_sparql(patterns) -> str:
    def term(x):
        return f"?v{x[1]}" if isinstance(x, tuple) else f"<{x}>"

    body = " . ".join(f"{term(s)} <{p}> {term(o)}" for s, p, o in patterns)
    return f"SELECT * WHERE {{ {body} }}"


class PoolExhausted(ValueError):
    """A run asked for more distinct instances than the pool holds."""


class Instances:
    """The instances of each template that fit, in an order drawn from a
    seed: fitting roots shuffled, one per distinct constant tuple.

    ``stream()`` and ``warmup()`` give disjoint instance sequences: the
    warm-up takes ``reserve`` instances of each template (at most half, on
    a small graph) from the back of its shuffled list, the window the
    others from the front.  Templates take turns in blocks that
    hold each template once, in a per-block order drawn from the seed, so
    every seed sends the same template mix.
    """

    def __init__(self, g: Graph, templates: list[Template], fit_rows: int,
                 seed: int, reserve: int):
        self.g = g
        self.templates = templates
        self.reserve = reserve
        rng = np.random.default_rng([seed, 1])
        self.roots, self.generated, self.kept = [], [], []
        for t in templates:
            valid = self._valid_roots(t)
            bound = self._fit_bound(t, valid)
            fits = valid[(bound <= fit_rows) & (bound < self._leaf_card(t))]
            self.generated.append(int(valid.shape[0]))
            self.kept.append(int(fits.shape[0]))
            self.roots.append(self._distinct(t, rng.permutation(fits)))
        self._order_rng = np.random.default_rng([seed, 2])
        self._order: list[int] = []

    def cut_share(self) -> float:
        """Share of generated instances (valid roots) the fit cut drops."""
        return 1.0 - sum(self.kept) / sum(self.generated)

    def _distinct(self, t: Template, roots: np.ndarray) -> np.ndarray:
        """The first root of each distinct constant tuple, in order: two
        roots with the same constants make the same query."""
        lo, _ = self.g.class_ranges[t.root_class]
        cols = [self.g.attr[p][roots - lo] for p, c in t.root if c]
        if not cols:
            return roots[:1]
        key = np.stack(cols, axis=1)
        _, first = np.unique(key, axis=0, return_index=True)
        return roots[np.sort(first)]

    def _valid_roots(self, t: Template) -> np.ndarray:
        """Root entities (ids) with every root predicate and at least one
        link edge to an entity with every leaf predicate: the witness
        generator's acceptance rule."""
        g = self.g
        lo, hi = g.class_ranges[t.root_class]
        ok = np.ones(hi - lo, bool)
        for p, _ in t.root:
            ok &= g.attr[p] >= 0
        tgt = g.pred_class[t.leaf[0]] if t.leaf else None
        ptr, obj = g.rel_ptr[t.link], g.rel_obj[t.link]
        edge_ok = np.ones(obj.shape[0], bool)
        if tgt is not None:
            t_lo, t_hi = g.class_ranges[tgt]
            leaf_ok = np.ones(t_hi - t_lo, bool)
            for p in t.leaf:
                leaf_ok &= g.attr[p] >= 0
            edge_ok = leaf_ok[obj - t_lo]
        good = np.concatenate([[0], np.cumsum(edge_ok)])
        ok &= (good[ptr[1:]] - good[ptr[:-1]]) > 0
        return np.nonzero(ok)[0] + lo

    def _fit_bound(self, t: Template, roots: np.ndarray) -> np.ndarray:
        """Per root: the larger of the rows matching the root star's most
        selective constant and the sum of their link out-degrees — a bound
        on every binding table of a plan that starts from that constant."""
        g = self.g
        lo, _ = g.class_ranges[t.root_class]
        ptr = g.rel_ptr[t.link]
        deg = (ptr[1:] - ptr[:-1]).astype(np.float64)
        best_card = np.full(roots.shape[0], np.inf)
        best_deg = np.zeros(roots.shape[0])
        for p, is_const in t.root:
            if not is_const:
                continue
            col = g.attr[p]
            have = col >= 0
            base = col[have].min() if have.any() else 0
            v = col[have] - base
            card = np.bincount(v)
            degsum = np.bincount(v, weights=deg[have])
            mine = col[roots - lo] - base
            c, d = card[mine], degsum[mine]
            better = c < best_card
            best_card = np.where(better, c, best_card)
            best_deg = np.where(better, d, best_deg)
        return np.maximum(best_card, best_deg)

    def _leaf_card(self, t: Template) -> int:
        """The fewest triples of any leaf predicate: a root constant below
        it is the query's most selective pattern, where a selective-first
        plan starts."""
        return min(int((self.g.attr[p] >= 0).sum()) for p in t.leaf)

    def _instance(self, ti: int, root: int) -> Instance:
        t = self.templates[ti]
        lo, _ = self.g.class_ranges[t.root_class]
        consts = {p: int(self.g.attr[p][root - lo]) for p, c in t.root if c}
        pats, n_vars = instance_patterns(t, consts)
        return Instance(ti, to_sparql(pats), pats, n_vars)

    def _reserve(self, ti: int) -> int:
        """How many of the template's instances the warm-up takes: up to
        ``reserve``, and never more than half (small test graphs)."""
        return min(self.reserve, self.roots[ti].shape[0] // 2)

    def pool(self) -> int:
        """Distinct instances the window can send, over all templates."""
        return sum(r.shape[0] - self._reserve(ti)
                   for ti, r in enumerate(self.roots))

    def _template_of(self, i: int) -> int:
        k = len(self.templates)
        while len(self._order) <= i:
            self._order += list(self._order_rng.permutation(k))
        return int(self._order[i])

    def stream(self):
        """The window's instances in order, all distinct; raises
        ``PoolExhausted`` once a template has none left but the
        warm-up's."""
        used = [0] * len(self.templates)
        i = 0
        while True:
            ti = self._template_of(i)
            roots = self.roots[ti]
            if used[ti] >= roots.shape[0] - self._reserve(ti):
                raise PoolExhausted(
                    f"template {ti} has no unsent instance left after {i} "
                    f"requests (pool {self.pool()})")
            yield self._instance(ti, roots[used[ti]])
            used[ti] += 1
            i += 1

    def warmup(self) -> list[list[Instance]]:
        """Per template, the ``reserve`` instances no window sends."""
        return [[self._instance(ti, r)
                 for r in roots[::-1][:self._reserve(ti)]]
                for ti, roots in enumerate(self.roots)]
