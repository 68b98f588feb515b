"""The benchmark's WatDiv-like graph: the deployment's data, made from a seed.

A copy of the repository's WatDiv-like generator (``repro.rdf.watdiv``),
kept here so that no change to the program can move the data the
benchmark measures on.  Its schema is a small model after WatDiv (5
classes, 33 predicates), not WatDiv's own data model; the configuration
files list the differences.  It keeps that generator's schema (entity classes,
per-class attribute predicates with Zipf-distributed values, relation
predicates with power-law out-degree) and differs in one way: every
predicate's triple count is fixed by the configuration, and the seed only
chooses which entities carry which triples.

- An attribute is carried by exactly ``round(attr_share * n)`` entities of
  its class (the original draws each entity with probability
  ``attr_share``), and each entity's value is Zipf-drawn from the seed.
- A relation's out-degree multiset is drawn once from ``shape_seed``; the
  seed permutes it over the entities and draws each edge's target, with
  no target repeated for one subject (so no triple is lost to
  de-duplication).

So every seed gives the same number of triples per predicate, hence the
same array shapes on the device and the same plan signatures (the
planner orders patterns by predicate cardinality): runs with different
seeds share every compiled program.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

#: Entity classes: (name, count per scale unit, attribute predicates,
#: relations as (target class index, mean out-degree)).  The same schema
#: as ``repro.rdf.watdiv.WatDivConfig.classes``.
CLASSES = (
    ("User", 10, 5, ((2, 1.5), (1, 2.0))),
    ("Product", 25, 9, ((3, 1.0),)),
    ("Review", 30, 4, ((1, 1.0), (0, 1.0))),
    ("Retailer", 1, 6, ()),
    ("Website", 5, 3, ((1, 3.0),)),
)


@dataclass(frozen=True)
class GraphConfig:
    scale: int
    attr_values: int = 1000  # distinct values per attribute predicate
    zipf_a: float = 1.6  # attribute-value skew
    attr_share: float = 0.85  # share of a class's entities with an attribute
    max_degree: int = 40  # out-degree ceiling of a relation
    shape_seed: int = 7  # draws the relations' out-degree multisets

    @staticmethod
    def from_json(cfg: dict) -> "GraphConfig":
        g = cfg["graph"]
        return GraphConfig(scale=int(g["scale"]),
                           attr_values=int(g["attr_values"]),
                           zipf_a=float(g["zipf_a"]),
                           attr_share=float(g["attr_share"]),
                           max_degree=int(g["max_degree"]),
                           shape_seed=int(g["shape_seed"]))


@dataclass
class Graph:
    """The triples plus the schema maps the traffic generator needs.

    ``attr[p]`` is predicate ``p``'s value per entity of its class
    (``-1`` where the entity lacks it); ``rel_ptr[p]`` / ``rel_obj[p]``
    are relation ``p``'s out-edges as CSR over its class's entities.
    """

    s: np.ndarray
    p: np.ndarray
    o: np.ndarray
    n_terms: int
    n_predicates: int
    class_ranges: list[tuple[int, int]]
    attr_preds: list[list[int]]
    rel_preds: list[list[tuple[int, int]]]
    pred_class: dict[int, int]
    attr: dict[int, np.ndarray]
    rel_ptr: dict[int, np.ndarray]
    rel_obj: dict[int, np.ndarray]

    @property
    def n_triples(self) -> int:
        return int(self.s.shape[0])


def layout(cfg: GraphConfig):
    """Entity id ranges, predicate ids and the term count: no randomness."""
    class_ranges, nxt = [], 0
    for _, count, _, _ in CLASSES:
        class_ranges.append((nxt, nxt + count * cfg.scale))
        nxt += count * cfg.scale
    lit_base = nxt
    attr_preds, nxt_pred = [], 0
    for _, _, n_attr, _ in CLASSES:
        attr_preds.append(list(range(nxt_pred, nxt_pred + n_attr)))
        nxt_pred += n_attr
    rel_preds = []
    for _, _, _, rels in CLASSES:
        rel_preds.append([(nxt_pred + i, tgt) for i, (tgt, _) in
                          enumerate(rels)])
        nxt_pred += len(rels)
    n_terms = lit_base + nxt_pred * cfg.attr_values
    return class_ranges, lit_base, attr_preds, rel_preds, nxt_pred, n_terms


def _distinct_targets(rng, subj: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """Uniform targets in [lo, hi) for each edge, redrawn until no subject
    has the same target twice."""
    obj = rng.integers(lo, hi, size=subj.shape[0], dtype=np.int64)
    while True:
        order = np.lexsort((obj, subj))
        s_o, o_o = subj[order], obj[order]
        dup = np.nonzero((s_o[1:] == s_o[:-1]) & (o_o[1:] == o_o[:-1]))[0]
        if dup.size == 0:
            return obj
        redo = order[dup + 1]
        obj[redo] = rng.integers(lo, hi, size=redo.shape[0], dtype=np.int64)


def generate(cfg: GraphConfig, seed: int) -> Graph:
    (class_ranges, lit_base, attr_preds, rel_preds, n_pred,
     n_terms) = layout(cfg)
    rng = np.random.default_rng(seed)
    shape_rng = np.random.default_rng(cfg.shape_seed)
    ss, ps, os_ = [], [], []
    pred_class: dict[int, int] = {}
    attr: dict[int, np.ndarray] = {}
    rel_ptr: dict[int, np.ndarray] = {}
    rel_obj: dict[int, np.ndarray] = {}

    for ci, (lo, hi) in enumerate(class_ranges):
        n = hi - lo
        k = int(round(cfg.attr_share * n))
        for pid in attr_preds[ci]:
            pred_class[pid] = ci
            has = np.sort(rng.permutation(n)[:k])
            vals = np.minimum(rng.zipf(cfg.zipf_a, size=k),
                              cfg.attr_values) - 1
            obj = lit_base + pid * cfg.attr_values + vals
            col = np.full(n, -1, np.int64)
            col[has] = obj
            attr[pid] = col
            ss.append(has + lo)
            ps.append(np.full(k, pid, np.int64))
            os_.append(obj)

    for ci, (lo, hi) in enumerate(class_ranges):
        n = hi - lo
        for (pid, tgt), (_, deg) in zip(rel_preds[ci], CLASSES[ci][3]):
            pred_class[pid] = ci
            degs = np.minimum(shape_rng.geometric(1.0 / max(deg, 1e-6), n),
                              cfg.max_degree)
            degs = rng.permutation(degs)
            subj = np.repeat(np.arange(n, dtype=np.int64), degs)
            t_lo, t_hi = class_ranges[tgt]
            obj = _distinct_targets(rng, subj, t_lo, t_hi)
            order = np.lexsort((obj, subj))
            obj = obj[order]
            rel_ptr[pid] = np.concatenate([[0], np.cumsum(degs)])
            rel_obj[pid] = obj
            ss.append(subj + lo)
            ps.append(np.full(subj.shape[0], pid, np.int64))
            os_.append(obj)

    return Graph(s=np.concatenate(ss), p=np.concatenate(ps),
                 o=np.concatenate(os_), n_terms=n_terms, n_predicates=n_pred,
                 class_ranges=class_ranges, attr_preds=attr_preds,
                 rel_preds=rel_preds, pred_class=pred_class, attr=attr,
                 rel_ptr=rel_ptr, rel_obj=rel_obj)
