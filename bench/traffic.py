"""The load generator: query instances and their send times, from a seed.

A traffic mix is a data file under ``traffic/``; this module is the one
general generator that reads it.  Its parts:

- **templates** (in the mix file): star chains over the WatDiv schema.  A
  template names its root class and the root star's branches (predicate,
  and whether the object is a constant), then zero or more hops: a
  relation predicate from the current star's subject to the next star's
  subject, and that star's attribute predicates (none for a path).  The
  paper's four shapes are a chain of no hop (1-star), one (2-stars), two
  (3-stars), and 3-9 hops with empty stars (paths).  The shapes were drawn
  with the WatDiv witness generator (``repro.rdf.queries``) and frozen in
  the file, so no change to the program can move them.
- **the anchor**: what makes two instances of a template differ.  By
  default the root star's constants, read from the root entity; with
  ``"anchor": "subject"`` the root entity itself, as the constant subject
  of the root star and the first hop: a template with no constant, such
  as the paper's paths, would otherwise be a single query.
- **instances**: a template with constants.  As the witness generator
  does, an instance is rooted at a real entity of the root class that has
  every root predicate and a walk along the hops to entities with every
  star's predicates, so every instance has at least one answer.
- **the fit cut**: an instance is sent only if the benchmark's own host
  count bounds every binding table of a plan that starts at the anchor at
  the configuration's ``fit_rows``, and only if the anchor is the query's
  most selective pattern, so that a selective-first plan starts there
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

ANCHORS = ("constants", "subject")


@dataclass(frozen=True)
class Hop:
    rel: int  # relation predicate from the current star to the next
    star: tuple[int, ...]  # the next star's attribute predicates


@dataclass(frozen=True)
class Template:
    root_class: int
    root: tuple[tuple[int, bool], ...]  # (predicate, object is constant)
    hops: tuple[Hop, ...] = ()
    anchor: str = "constants"

    @staticmethod
    def from_json(t: dict) -> "Template":
        """A template from its mix entry.  ``link`` and ``leaf`` are one
        hop, the 2-stars shape; ``hops`` is a list of ``{"rel", "star"}``."""
        if "link" in t:
            hops = (Hop(int(t["link"]), tuple(int(p) for p in t["leaf"])),)
        else:
            hops = tuple(Hop(int(h["rel"]), tuple(int(p) for p in h["star"]))
                         for h in t.get("hops", ()))
        tpl = Template(int(t["root_class"]),
                       tuple((int(p), bool(c)) for p, c in t["root"]),
                       hops, t.get("anchor", "constants"))
        if tpl.anchor not in ANCHORS:
            raise ValueError(f"anchor {tpl.anchor!r} is not one of {ANCHORS}")
        if (tpl.anchor == "subject") == bool(tpl.const_branches()):
            raise ValueError("a template is anchored either at root "
                             "constants or, with none, at its subject")
        return tpl

    def const_branches(self) -> list[int]:
        return [p for p, c in self.root if c]


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


def instance_patterns(t: Template, consts: dict[int, int],
                      subject: int | None = None):
    """Triple patterns of template ``t`` with root constants ``consts``
    (predicate -> object id) and, for a subject anchor, the root entity
    ``subject``.  Variables are numbered by first appearance, which is
    how the endpoint's parser numbers them."""
    pats, nxt = [], 0
    if subject is None:
        cur, nxt = ("v", 0), 1
    else:
        cur = subject
    for p, is_const in t.root:
        if is_const:
            pats.append((cur, p, consts[p]))
        else:
            pats.append((cur, p, ("v", nxt)))
            nxt += 1
    for hop in t.hops:
        y = ("v", nxt)
        pats.append((cur, hop.rel, y))
        nxt += 1
        for p in hop.star:
            pats.append((y, p, ("v", nxt)))
            nxt += 1
        cur = y
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
    seed: fitting roots shuffled, one per distinct anchor.  ``roots[t]``
    and ``bounds[t]`` hold template ``t``'s roots in that order and each
    one's table bound.

    ``stream()`` and ``warmup()`` give disjoint instance sequences: the
    warm-up takes ``reserve`` instances of each template (at most half, on
    a small graph) from the back of its shuffled list, the window the
    others from the front.  Where a template's largest bound passes the
    engine's starting capacity ``cap``, that instance leaves the list for
    ``past_cap()``, which the warm-up serves and the window never does:
    tables past ``cap`` make the program run at a larger capacity, and
    the warm-up is where that should compile.  Templates take turns in
    blocks that hold each template once, in a per-block order drawn from
    the seed, so every seed sends the same template mix.
    """

    def __init__(self, g: Graph, templates: list[Template], fit_rows: int,
                 seed: int, reserve: int, cap: int | None = None):
        self.g = g
        self.templates = templates
        self.reserve = reserve
        rng = np.random.default_rng([seed, 1])
        self.roots, self.bounds, self.generated, self.kept = [], [], [], []
        self._past_cap: list[tuple[int, int]] = []  # (template, root)
        for t in templates:
            valid = self._valid_roots(t)
            bound, card = self._fit_bound(t, valid)
            fits = valid[(bound <= fit_rows)
                         & (card < self._others_card(t))]
            self.generated.append(int(valid.shape[0]))
            self.kept.append(int(fits.shape[0]))
            roots = self._distinct(t, rng.permutation(fits))
            bounds = bound[np.searchsorted(valid, roots)]
            if cap is not None and roots.shape[0] and bounds.max() > cap:
                big = int(np.argmax(bounds))
                self._past_cap.append((len(self.roots), int(roots[big])))
                roots, bounds = np.delete(roots, big), np.delete(bounds, big)
            self.roots.append(roots)
            self.bounds.append(bounds)
        self._order_rng = np.random.default_rng([seed, 2])
        self._order: list[int] = []

    def cut_share(self) -> float:
        """Share of generated instances (valid roots) the fit cut drops."""
        return 1.0 - sum(self.kept) / sum(self.generated)

    # ------------------------------------------------------------ the graph
    def _lo(self, ci: int) -> int:
        return self.g.class_ranges[ci][0]

    def _levels(self, t: Template) -> list[int]:
        """The class of each star along the chain, the root's first."""
        classes = [t.root_class]
        for hop in t.hops:
            tgt = dict(self.g.rel_preds[classes[-1]]).get(hop.rel)
            if tgt is None:
                raise ValueError(f"predicate {hop.rel} is no relation of "
                                 f"class {classes[-1]}")
            classes.append(tgt)
        return classes

    def _carries(self, ci: int, preds) -> np.ndarray:
        """Per entity of class ``ci``: whether it has every predicate."""
        lo, hi = self.g.class_ranges[ci]
        ok = np.ones(hi - lo, bool)
        for p in preds:
            ok &= self.g.attr[p] >= 0
        return ok

    def _per_subject(self, rel: int, ci_to: int,
                     vals: np.ndarray) -> np.ndarray:
        """Per subject of relation ``rel``: the sum of ``vals`` (indexed
        by the target class's entities) over its out-edges."""
        ptr, obj = self.g.rel_ptr[rel], self.g.rel_obj[rel]
        cs = np.concatenate([[0], np.cumsum(vals[obj - self._lo(ci_to)])])
        return cs[ptr[1:]] - cs[ptr[:-1]]

    def _valid_roots(self, t: Template) -> np.ndarray:
        """Root entities (ids) with every root predicate and a walk along
        the hops to entities with every star's predicates: the witness
        generator's acceptance rule."""
        classes = self._levels(t)
        reach = None  # per entity of the next level: a walk goes on
        for i in range(len(t.hops), 0, -1):
            ok = self._carries(classes[i], t.hops[i - 1].star)
            if reach is not None:
                ok &= reach
            reach = self._per_subject(t.hops[i - 1].rel, classes[i],
                                      ok.astype(np.int64)) > 0
        ok = self._carries(t.root_class, [p for p, _ in t.root])
        if reach is not None:
            ok &= reach
        return np.nonzero(ok)[0] + self._lo(t.root_class)

    def _walks(self, t: Template, classes: list[int]) -> list[np.ndarray]:
        """Per entity of the root class and per hop ``k``: the rows a
        plan from that entity holds after hop ``k``'s probe.  Each hop
        emits one row per out-edge; each star after it keeps the rows
        whose entity has the star's predicates (attributes are
        single-valued, so only hops multiply)."""
        out = []
        for k in range(len(t.hops), 0, -1):
            rel = t.hops[k - 1].rel
            ptr = self.g.rel_ptr[rel]
            rows = (ptr[1:] - ptr[:-1]).astype(np.float64)
            for j in range(k - 1, 0, -1):
                keep = self._carries(classes[j], t.hops[j - 1].star)
                rows = self._per_subject(t.hops[j - 1].rel, classes[j],
                                         np.where(keep, rows, 0.0))
            out.append(rows)
        return out[::-1]

    def _fit_bound(self, t: Template, roots: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
        """Per root: a bound on every binding table of a plan that starts
        at the anchor, and the anchor's own row count.

        The rows start at the entities the anchor matches: the one root
        for a subject anchor, else the entities with the root star's most
        selective constant.  Each table is then the sum, over those
        entities, of the rows their walks hold after each hop; the bound
        is the largest."""
        lo = self._lo(t.root_class)
        walks = self._walks(t, self._levels(t))
        if t.anchor == "subject":
            ones = np.ones(roots.shape[0])
            return np.maximum.reduce(
                [ones] + [w[roots - lo] for w in walks]), ones
        best_card = np.full(roots.shape[0], np.inf)
        best_rows = np.zeros(roots.shape[0])
        for p in t.const_branches():
            col = self.g.attr[p]
            have = col >= 0
            base = col[have].min() if have.any() else 0
            v = col[have] - base
            card = np.bincount(v)
            rows = [np.bincount(v, weights=w[have], minlength=card.shape[0])
                    for w in walks]
            mine = col[roots - lo] - base
            c = card[mine].astype(np.float64)
            r = np.maximum.reduce([c] + [x[mine] for x in rows])
            better = c < best_card
            best_card = np.where(better, c, best_card)
            best_rows = np.where(better, r, best_rows)
        return best_rows, best_card

    def _others_card(self, t: Template) -> float:
        """The fewest triples of any predicate outside the root star's
        constants: an anchor that matches fewer is the query's most
        selective pattern, where a selective-first plan starts."""
        g = self.g
        preds = [p for p, c in t.root if not c]
        preds += [p for hop in t.hops for p in (hop.rel, *hop.star)]
        counts = [g.rel_obj[p].shape[0] if p in g.rel_obj
                  else int((g.attr[p] >= 0).sum()) for p in preds]
        return float(min(counts, default=np.inf))

    # ----------------------------------------------------------- instances
    def _distinct(self, t: Template, roots: np.ndarray) -> np.ndarray:
        """The first root of each distinct anchor, in order: two roots
        with the same constants make the same query."""
        if t.anchor == "subject":
            return roots
        lo = self._lo(t.root_class)
        key = np.stack([self.g.attr[p][roots - lo]
                        for p in t.const_branches()], axis=1)
        _, first = np.unique(key, axis=0, return_index=True)
        return roots[np.sort(first)]

    def _instance(self, ti: int, root: int) -> Instance:
        t = self.templates[ti]
        if t.anchor == "subject":
            pats, n_vars = instance_patterns(t, {}, subject=int(root))
        else:
            lo = self._lo(t.root_class)
            pats, n_vars = instance_patterns(
                t, {p: int(self.g.attr[p][root - lo])
                    for p in t.const_branches()})
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

    def past_cap(self) -> list[Instance]:
        """Each template's largest-bound instance where that bound passes
        ``cap``; no window sends them."""
        return [self._instance(ti, r) for ti, r in self._past_cap]
