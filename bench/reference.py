"""The plain reference: basic graph patterns evaluated with numpy.

It reads only the triples the benchmark generated (``watdiv.Graph``) and
imports nothing of the program.  Evaluation is a left-deep join, one
pattern at a time over two sorted copies of the triples, by ``(p, s)`` and
by ``(p, o)``: a constant-object pattern first (else a constant-subject
one), then the others in the order given, each joined on a variable
already bound or looked up by its constant subject.  Answers are
compared as sets of rows in a canonical (lexicographic) order, so the
reference need not reproduce the server's row order.

A pattern is ``(s, p, o)`` where ``p`` is a predicate id and ``s`` / ``o``
are either a constant id or ``("v", i)``.
"""

from __future__ import annotations

import numpy as np


class Reference:
    def __init__(self, s: np.ndarray, p: np.ndarray, o: np.ndarray,
                 n_terms: int):
        s, p, o = (np.asarray(a, np.int64) for a in (s, p, o))
        self.r = np.int64(n_terms)
        by_ps = np.lexsort((o, s, p))
        self.ps_key = p[by_ps] * self.r + s[by_ps]
        self.ps_o = o[by_ps]
        by_po = np.lexsort((s, o, p))
        self.po_key = p[by_po] * self.r + o[by_po]
        self.po_s = s[by_po]

    def _runs(self, keys, want):
        lo = np.searchsorted(keys, want, "left")
        hi = np.searchsorted(keys, want, "right")
        return lo, hi

    def _expand(self, cols, lo, hi, new_vals):
        """Repeat every binding row once per position of its run."""
        n = hi - lo
        rows = np.repeat(np.arange(n.shape[0]), n)
        pos = np.arange(rows.shape[0]) - np.repeat(np.cumsum(n) - n, n) \
            + np.repeat(lo, n)
        return {k: v[rows] for k, v in cols.items()}, new_vals[pos]

    def answers(self, patterns, n_vars: int) -> np.ndarray:
        """Every solution of the pattern set, one row per solution, columns
        in variable order, sorted lexicographically."""
        cols: dict[int, np.ndarray] | None = None
        for s_t, p, o_t in join_order(patterns):
            s_var = s_t[1] if isinstance(s_t, tuple) else None
            o_var = o_t[1] if isinstance(o_t, tuple) else None
            if cols is None:
                cols = self._first(s_t, p, o_t, s_var, o_var)
            elif s_var is None or s_var in cols:
                n = next(iter(cols.values())).shape[0]
                subj = cols[s_var] if s_var is not None \
                    else np.full(n, s_t, np.int64)
                lo, hi = self._runs(self.ps_key, p * self.r + subj)
                cols, objs = self._expand(cols, lo, hi, self.ps_o)
                if o_var is None:
                    keep = objs == o_t
                elif o_var in cols:
                    keep = objs == cols[o_var]
                else:
                    keep = None
                    cols[o_var] = objs
                if keep is not None:
                    cols = {k: v[keep] for k, v in cols.items()}
            elif o_var is not None and o_var in cols:
                lo, hi = self._runs(self.po_key, p * self.r + cols[o_var])
                cols, subs = self._expand(cols, lo, hi, self.po_s)
                cols[s_var] = subs
            else:
                raise ValueError("patterns must join on a bound variable")
        out = np.stack([cols[i] for i in range(n_vars)], axis=1) \
            if n_vars else np.zeros((0, 0), np.int64)
        return canonical(out)

    def _first(self, s_t, p, o_t, s_var, o_var):
        if o_var is None:
            lo, hi = self._runs(self.po_key, np.array([p * self.r + o_t]))
            subs = self.po_s[lo[0]:hi[0]]
            if s_var is None:
                raise ValueError("a fully constant pattern is not a query")
            return {s_var: subs.copy()}
        if s_var is None:
            lo, hi = self._runs(self.ps_key, np.array([p * self.r + s_t]))
            return {o_var: self.ps_o[lo[0]:hi[0]].copy()}
        lo = np.searchsorted(self.ps_key, p * self.r, "left")
        hi = np.searchsorted(self.ps_key, (p + 1) * self.r, "left")
        subs = self.ps_key[lo:hi] - p * self.r
        cols = {o_var: self.ps_o[lo:hi].copy()}
        cols[s_var] = subs
        return cols


def join_order(patterns) -> list:
    """A constant-object pattern first (the smallest starting set), else a
    constant-subject one; then each next pattern in the given order that
    shares a bound variable or has a constant subject."""
    def const(x):
        return not isinstance(x, tuple)

    rest = list(patterns)
    first = next((t for t in rest if const(t[2])),
                 next((t for t in rest if const(t[0])), rest[0]))
    rest.remove(first)
    order, bound = [first], {x[1] for x in (first[0], first[2])
                             if isinstance(x, tuple)}
    while rest:
        nxt = next(t for t in rest if const(t[0]) or any(
            isinstance(x, tuple) and x[1] in bound for x in (t[0], t[2])))
        rest.remove(nxt)
        order.append(nxt)
        bound |= {x[1] for x in (nxt[0], nxt[2]) if isinstance(x, tuple)}
    return order


def canonical(rows: np.ndarray) -> np.ndarray:
    """Rows as int64, sorted lexicographically (first column first)."""
    rows = np.asarray(rows, np.int64)
    if rows.ndim != 2 or rows.shape[0] == 0:
        return rows.reshape(-1, rows.shape[1] if rows.ndim == 2 else 0)
    order = np.lexsort(rows.T[::-1])
    return rows[order]
