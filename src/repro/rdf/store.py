"""HDT-style triple store: sorted permutation indexes over dictionary ids.

Layout
------
Triples are dictionary-encoded ``(s, p, o)`` int32 triples.  We materialise
two sort orders (HDT materialises SPO + optional secondary indexes; the SPF
server's access paths need exactly these two):

- **PSO order** — sorted by ``(p, s, o)``.  A predicate's triples form a
  contiguous run (CSR ``pred_offsets``); within the run subjects are sorted,
  so ``(?s, p, ?o)`` yields a *sorted* subject list and ``(s, p, ?o)`` is a
  binary-search run.  Star-pattern evaluation intersects these sorted subject
  lists — the paper's "stars are linear for the server" property maps to
  merge-intersection of sorted runs.
- **POS order** — sorted by ``(p, o, s)``.  ``(?s, p, o)`` is a binary-search
  run whose subjects are sorted — again merge-intersectable.

Composite keys (``p*R + s`` etc.) make every lookup a vectorised
``searchsorted``.  They are **int32**: the TPU's vector units have no
64-bit integers, so the probe kernels take int32 keys.  ``build`` checks
the radix product against the int32 bound and refuses a graph past it
(WatDiv at ~10M triples needs ~5e7 of the ~2.1e9 available).

The store keeps **numpy** copies for host-side query planning (join ordering
uses exact run lengths — the Def. 6 cardinality metadata with eps = 0) and
**jax** device arrays for evaluation.  ``shard_by_subject`` hash-partitions
the store for the distributed runtime: every star pattern's matches share a
subject, so subject hashing makes server-side star joins collective-free.

Write path: the delta overlay
-----------------------------
The *base* index above is immutable — it is only ever rebuilt wholesale —
but the store itself is **writable** through a small sorted delta overlay
(``apply_delta`` / ``insert_triples`` / ``delete_triples``):

- **inserts** live in a second pair of sorted runs in the same PSO/POS
  composite-key layout (``h_ins_key_ps`` …), disjoint from the live base
  by construction;
- **deletes** of base triples become **tombstones**: sorted arrays of base
  *positions* (one per index order, ``h_tomb_pos_ps`` / ``h_tomb_pos_po``)
  plus the precomputed nondecreasing ``pos - rank`` column
  (``h_tomb_adj_*``) that turns "k-th live base row" into one
  ``searchsorted`` (see ``kernels/ops.delta_probe``'s consumers).

Every probe then becomes a *merged eqrange over base + delta* — the second
probe costs ``O(log delta)``, not ``O(log store)`` — and the logical triple
set is always ``base - tombstones + inserts``.  ``compact()`` folds the
delta into the base (the only remaining full re-sort) off the serving path;
``maybe_compact`` gates it on a delta-size threshold.

Epochs: ``epoch`` advances on every logical change.  A **delta-only** bump
keeps the uploaded base device arrays (only the small delta arrays are
re-uploaded); ``compact``/``bump_epoch`` drop the whole device view.  Each
bump logs the set of predicates it touched (``changed_preds_since``), which
is what lets the fragment cache and capacity planner *carry over* entries
whose predicate runs the delta never touched instead of sweeping them.
The dictionary itself is fixed: inserts must use existing term/predicate
ids (growing the dictionary is a rebuild, not a delta).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

_INT32_MAX = np.int32(np.iinfo(np.int32).max)
# every composite key and probe bound is < (n_predicates + 1) * n_terms
# (one predicate id past the last is the shard padding predicate), and
# int32 max is the key padding — so the product must stay below it
KEY_LIMIT = 2**31 - 1
_EPOCH_LOG_MAX = 64


class StoreArrays(NamedTuple):
    """Device-resident index arrays (a pytree; safe to close over in jit).

    Base arrays: padded entries (if any) sort to the end with key = +max
    and never fall inside a real predicate/key run.

    Delta arrays: ``ins_*`` mirror the base layout over the insert set
    (padded with key = +max); ``tomb_pos_*`` are sorted base positions of
    tombstoned rows per order (padded with the base length, which no real
    position reaches) and ``tomb_adj_*`` the precomputed nondecreasing
    ``pos - rank`` column (padded with int32 max) — together they answer
    "tombstones below position q" and "k-th live base position" with one
    ``searchsorted`` each.  Zero-length delta arrays are the trace-time
    static that keeps the no-delta fast path byte-for-byte the old code.
    """

    # PSO order (base)
    key_ps_pso: jnp.ndarray  # int32[n]  p*R_term + s, ascending
    s_pso: jnp.ndarray  # int32[n]
    o_pso: jnp.ndarray  # int32[n]
    # POS order (base)
    key_po_pos: jnp.ndarray  # int32[n]  p*R_term + o, ascending
    s_pos: jnp.ndarray  # int32[n]
    o_pos: jnp.ndarray  # int32[n]  (object of each POS row; run-constant)
    # delta: inserts, PSO order
    ins_key_ps: jnp.ndarray  # int32[m]
    ins_s_pso: jnp.ndarray  # int32[m]
    ins_o_pso: jnp.ndarray  # int32[m]
    # delta: inserts, POS order
    ins_key_po: jnp.ndarray  # int32[m]
    ins_s_pos: jnp.ndarray  # int32[m]
    ins_o_pos: jnp.ndarray  # int32[m]
    # delta: tombstones (sorted base positions + pos-rank columns)
    tomb_pos_ps: jnp.ndarray  # int32[t]
    tomb_adj_ps: jnp.ndarray  # int32[t]  tomb_pos - arange(t), nondecreasing
    tomb_pos_po: jnp.ndarray  # int32[t]
    tomb_adj_po: jnp.ndarray  # int32[t]


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


@dataclass
class TripleStore:
    """Dictionary-id triple store: immutable PSO/POS base + delta overlay.

    ``n_triples`` is the **logical** live count (base - tombstones +
    inserts); ``n_base`` the physical base index length.  The ``h_*``
    arrays are the base index; the delta lives in ``h_ins_*`` /
    ``h_tomb_*`` (rebuilt from the canonical insert/tombstone sets on
    every ``apply_delta`` — delta-sized work, never a base re-sort).
    """

    n_triples: int
    n_terms: int  # radix for subject/object ids (shared id space)
    n_predicates: int
    # host (numpy) copies for planning
    h_key_ps: np.ndarray
    h_s_pso: np.ndarray
    h_o_pso: np.ndarray
    h_key_po: np.ndarray
    h_s_pos: np.ndarray
    h_o_pos: np.ndarray
    h_pred_offsets: np.ndarray  # int64[n_predicates + 2] CSR (PSO==POS runs)
    # mutation epoch: advanced on every logical triple-set change (delta
    # application, compaction, or an external ``bump_epoch``), so
    # epoch-tagged fragment-cache entries and planner records computed
    # against the old contents can never be served stale.  Delta writes go
    # through ``apply_delta`` (delta-only bump: base device arrays are
    # kept, predicates touched are logged for warm carry-over);
    # ``compact`` folds the delta into the base; the public
    # ``bump_epoch`` remains the legacy full-drop seam for external
    # mutation of the host arrays.
    epoch: int = 0
    # physical length of the base index (== n_triples while the delta is
    # empty); -1 = derive from n_triples in __post_init__
    n_base: int = -1
    # bumped only when the *base* arrays change (build/compact/bump_epoch):
    # versions caches of base-derived state (device base upload, shard
    # partitions, degree statistics) across delta-only epochs
    base_epoch: int = 0
    # device copies (built lazily)
    _device: StoreArrays | None = field(default=None, repr=False)
    _device_epoch: int = field(default=-1, repr=False)
    _dev_base: tuple | None = field(default=None, repr=False)
    _dev_base_epoch: int = field(default=-1, repr=False)
    # canonical delta state: sets of (p, s, o) int tuples
    _ins_set: set = field(default_factory=set, repr=False)
    _tomb_set: set = field(default_factory=set, repr=False)
    # derived sorted delta arrays (see _rebuild_delta)
    h_ins_key_ps: np.ndarray | None = field(default=None, repr=False)
    h_ins_s_pso: np.ndarray | None = field(default=None, repr=False)
    h_ins_o_pso: np.ndarray | None = field(default=None, repr=False)
    h_ins_key_po: np.ndarray | None = field(default=None, repr=False)
    h_ins_s_pos: np.ndarray | None = field(default=None, repr=False)
    h_ins_o_pos: np.ndarray | None = field(default=None, repr=False)
    h_tomb_pos_ps: np.ndarray | None = field(default=None, repr=False)
    h_tomb_adj_ps: np.ndarray | None = field(default=None, repr=False)
    h_tomb_pos_po: np.ndarray | None = field(default=None, repr=False)
    h_tomb_adj_po: np.ndarray | None = field(default=None, repr=False)
    # (epoch, frozenset of touched predicate ids | None) per bump, bounded
    _epoch_log: list = field(default_factory=list, repr=False)
    # base shard partitions, keyed by n_shards (cleared on base changes);
    # values: (shards, delta_epoch_applied)
    _shard_cache: dict = field(default_factory=dict, repr=False)

    def __post_init__(self):
        if self.n_base < 0:
            self.n_base = self.n_triples
        if self.h_ins_key_ps is None:
            self._rebuild_delta()

    # ------------------------------------------------------------------ build
    @staticmethod
    def build(s: np.ndarray, p: np.ndarray, o: np.ndarray, n_terms: int | None = None,
              n_predicates: int | None = None) -> "TripleStore":
        s = np.asarray(s, dtype=np.int64)
        p = np.asarray(p, dtype=np.int64)
        o = np.asarray(o, dtype=np.int64)
        if n_terms is None:
            n_terms = int(max(s.max(initial=0), o.max(initial=0))) + 1
        if n_predicates is None:
            n_predicates = int(p.max(initial=0)) + 1
        n = s.shape[0]
        r = np.int64(n_terms)
        check_key_radix(n_terms, n_predicates)

        # deduplicate (RDF graphs are triple *sets*)
        pso = np.stack([p, s, o], axis=1)
        pso = np.unique(pso, axis=0)  # sorts lexicographically by (p, s, o)
        p_, s_, o_ = pso[:, 0], pso[:, 1], pso[:, 2]
        n = p_.shape[0]
        key_ps = (p_ * r + s_).astype(np.int32)

        order_pos = np.lexsort((s_, o_, p_))  # sort by (p, o, s)
        s_pos = s_[order_pos]
        o_pos = o_[order_pos]
        key_po = (p_[order_pos] * r + o_pos).astype(np.int32)

        # CSR over predicates (same boundaries in both orders).
        pred_offsets = np.searchsorted(p_, np.arange(n_predicates + 2))
        return TripleStore(
            n_triples=int(n),
            n_terms=int(n_terms),
            n_predicates=int(n_predicates),
            h_key_ps=key_ps,
            h_s_pso=s_.astype(np.int32),
            h_o_pso=o_.astype(np.int32),
            h_key_po=key_po,
            h_s_pos=s_pos.astype(np.int32),
            h_o_pos=o_pos.astype(np.int32),
            h_pred_offsets=pred_offsets.astype(np.int64),
        )

    # ------------------------------------------------------------- device view
    @property
    def device(self) -> StoreArrays:
        """Lazily uploaded device view, rebuilt per epoch.

        The base upload is versioned separately (``base_epoch``): a
        delta-only epoch re-uploads only the (pow2-padded) delta arrays
        and reuses the resident base arrays — the "don't re-upload the
        unchanged base on a delta-only epoch" half of the write path.
        """
        if self._device is None or self._device_epoch != self.epoch:
            if self._dev_base is None \
                    or self._dev_base_epoch != self.base_epoch:
                self._dev_base = (
                    jnp.asarray(self.h_key_ps),
                    jnp.asarray(self.h_s_pso),
                    jnp.asarray(self.h_o_pso),
                    jnp.asarray(self.h_key_po),
                    jnp.asarray(self.h_s_pos),
                    jnp.asarray(self.h_o_pos),
                )
                self._dev_base_epoch = self.base_epoch
            m = int(self.h_ins_key_ps.shape[0])
            t = int(self.h_tomb_pos_ps.shape[0])
            delta = self._delta_host_padded(self._delta_bucket(m),
                                            self._delta_bucket(t))
            self._device = StoreArrays(
                *self._dev_base, *(jnp.asarray(a) for a in delta))
            self._device_epoch = self.epoch
        return self._device

    def _delta_bucket(self, n: int) -> int:
        """Padded device length for a delta column of ``n`` live entries.

        A non-empty delta pads to one *stable* bucket — pow2 of
        ``max(n, n_base // 4)`` — instead of its own pow2.  The floor is
        the default ``maybe_compact`` threshold: every delta epoch
        between two compactions then shares a single trace-time shape,
        so serving pays one unit-step compile when the first write
        arrives and none for subsequent deltas (growth past the floor
        would re-trace, but at that point compaction is due anyway).
        Zero stays zero: the empty delta is the static the no-delta
        fast path specializes on.
        """
        return _pow2(max(n, max(1, self.n_base // 4))) if n else 0

    def _delta_host_padded(self, m_pad: int, t_pad: int) -> tuple:
        """The 10 host delta arrays padded to ``(m_pad, t_pad)`` lengths.

        Padding values keep every consumer exact: insert keys pad with
        int32 max (outside any real eqrange), insert value columns with 0
        (never gathered — runs exclude padding), tombstone positions with
        ``n_base`` (no real base position reaches it, and the counts use
        strict ``<``), and the adj column with int32 max (a live rank
        ``k < n_base`` never counts it, and nondecreasingness holds).
        """
        def pad(a, n, val):
            if a.shape[0] >= n:
                return a
            return np.concatenate([a, np.full(n - a.shape[0], val, a.dtype)])

        return (
            pad(self.h_ins_key_ps, m_pad, _INT32_MAX),
            pad(self.h_ins_s_pso, m_pad, 0),
            pad(self.h_ins_o_pso, m_pad, 0),
            pad(self.h_ins_key_po, m_pad, _INT32_MAX),
            pad(self.h_ins_s_pos, m_pad, 0),
            pad(self.h_ins_o_pos, m_pad, 0),
            pad(self.h_tomb_pos_ps, t_pad, np.int32(self.n_base)),
            pad(self.h_tomb_adj_ps, t_pad, _INT32_MAX),
            pad(self.h_tomb_pos_po, t_pad, np.int32(self.n_base)),
            pad(self.h_tomb_adj_po, t_pad, _INT32_MAX),
        )

    @property
    def radix(self) -> int:
        return self.n_terms

    @property
    def delta_size(self) -> int:
        """Inserts + tombstones currently overlaid on the base."""
        return len(self._ins_set) + len(self._tomb_set)

    def bump_epoch(self) -> int:
        """Advance the mutation epoch after an *external* change.

        The legacy full-drop seam: callers that mutated the host arrays
        directly get the old contract — the whole device view (base
        included) is dropped and re-uploaded, shard partitions are
        rebuilt, and the change is logged as touching an *unknown*
        predicate set, so every cache/planner entry is swept (no carry
        -over).  The delta write path (``apply_delta``) bumps through its
        own delta-aware route instead.  Returns the new epoch.
        """
        self.base_epoch += 1
        return self._bump(None, delta_only=False)

    def _bump(self, changed: frozenset | None, *, delta_only: bool) -> int:
        self.epoch += 1
        self._device = None
        if not delta_only:
            self._dev_base = None
            self._shard_cache.clear()
        self._epoch_log.append((self.epoch, changed))
        if len(self._epoch_log) > _EPOCH_LOG_MAX:
            del self._epoch_log[0]
        return self.epoch

    def changed_preds_since(self, epoch: int) -> frozenset | None:
        """Union of predicate ids touched by every bump after ``epoch``.

        ``frozenset()`` when nothing changed (epoch is current, or only
        content-preserving bumps like compaction happened); ``None`` when
        the answer is unknown (an external ``bump_epoch`` in the window,
        or the bounded log no longer covers ``epoch``) — callers must
        treat ``None`` as "everything changed" and sweep.
        """
        if epoch == self.epoch:
            return frozenset()
        if epoch > self.epoch:
            return None
        acc: set = set()
        seen_down_to = self.epoch + 1
        for e, ch in reversed(self._epoch_log):
            if e <= epoch:
                break
            if e != seen_down_to - 1 or ch is None:
                return None  # gap in the log, or an unknown-change bump
            acc |= ch
            seen_down_to = e
        if seen_down_to != epoch + 1:
            return None  # the bounded log was truncated past `epoch`
        return frozenset(acc)

    # ------------------------------------------------------------- write path
    def key(self, p: int, term: int) -> np.int32:
        """Composite key ``p * n_terms + term`` as an int32 scalar.

        Host lookups must probe the int32 key columns with an int32
        scalar: numpy widens the whole column to the probe's dtype on
        every mixed-dtype ``searchsorted``.
        """
        return np.int32(int(p) * self.n_terms + int(term))

    def _base_pos_ps(self, p: int, s: int, o: int) -> int:
        """PSO position of a base triple, or -1 if absent from the base."""
        key = self.key(p, s)
        lo = int(np.searchsorted(self.h_key_ps, key, side="left"))
        hi = int(np.searchsorted(self.h_key_ps, key, side="right"))
        j = int(np.searchsorted(self.h_o_pso[lo:hi], o, side="left"))
        if lo + j < hi and int(self.h_o_pso[lo + j]) == o:
            return lo + j
        return -1

    def _base_pos_po(self, p: int, s: int, o: int) -> int:
        """POS position of a base triple (caller guarantees presence)."""
        key = self.key(p, o)
        lo = int(np.searchsorted(self.h_key_po, key, side="left"))
        hi = int(np.searchsorted(self.h_key_po, key, side="right"))
        j = int(np.searchsorted(self.h_s_pos[lo:hi], s, side="left"))
        return lo + j

    def apply_delta(self, insert=None, delete=None) -> int:
        """Apply a write batch to the delta overlay; returns the epoch.

        ``insert`` / ``delete`` are ``(s, p, o)`` array triples like
        ``build``'s.  Deletes apply first, then inserts.  Semantics are
        set-semantics on the logical triple set: deleting an insert
        removes it, deleting a live base triple tombstones it, deleting
        an absent triple is a no-op; inserting a tombstoned triple
        cancels the tombstone, inserting a live triple is a no-op.
        Ineffective batches do not bump the epoch.

        Work is O(batch · log base + delta · log delta) — the base is
        never re-sorted.  The bump is delta-only (base device arrays are
        kept resident) and logs the touched predicate ids for warm
        cache/planner carry-over.  Ids must be inside the fixed
        dictionary (``n_terms`` / ``n_predicates``).
        """
        changed: set[int] = set()

        def _rows(batch):
            s, p, o = (np.asarray(a, np.int64).ravel() for a in batch)
            if s.shape != p.shape or s.shape != o.shape:
                raise ValueError("insert/delete arrays must align")
            return zip(p.tolist(), s.tolist(), o.tolist())

        if delete is not None:
            for t in _rows(delete):
                if t in self._ins_set:
                    self._ins_set.remove(t)
                    changed.add(t[0])
                elif t not in self._tomb_set \
                        and self._base_pos_ps(*t) >= 0:
                    self._tomb_set.add(t)
                    changed.add(t[0])
        if insert is not None:
            for t in _rows(insert):
                p, s, o = t
                if not (0 <= p < self.n_predicates and 0 <= s < self.n_terms
                        and 0 <= o < self.n_terms):
                    raise ValueError(
                        f"triple {(s, p, o)} outside the fixed dictionary "
                        f"(n_terms={self.n_terms}, "
                        f"n_predicates={self.n_predicates}); growing the "
                        f"dictionary is a rebuild, not a delta")
                if t in self._tomb_set:
                    self._tomb_set.remove(t)
                    changed.add(p)
                elif t not in self._ins_set and self._base_pos_ps(*t) < 0:
                    self._ins_set.add(t)
                    changed.add(p)
        if not changed:
            return self.epoch
        self._rebuild_delta()
        return self._bump(frozenset(changed), delta_only=True)

    def insert_triples(self, s, p, o) -> int:
        return self.apply_delta(insert=(s, p, o))

    def delete_triples(self, s, p, o) -> int:
        return self.apply_delta(delete=(s, p, o))

    def _rebuild_delta(self) -> None:
        """Re-derive the sorted delta arrays from the canonical sets
        (delta-sized sorts; the base arrays are untouched)."""
        r = np.int64(self.n_terms)
        ins = np.array(sorted(self._ins_set), np.int64).reshape(-1, 3)
        p_, s_, o_ = ins[:, 0], ins[:, 1], ins[:, 2]
        # (p, s, o) sort == PSO layout
        self.h_ins_key_ps = (p_ * r + s_).astype(np.int32)
        self.h_ins_s_pso = s_.astype(np.int32)
        self.h_ins_o_pso = o_.astype(np.int32)
        order_pos = np.lexsort((s_, o_, p_))
        self.h_ins_key_po = (p_[order_pos] * r
                             + o_[order_pos]).astype(np.int32)
        self.h_ins_s_pos = s_[order_pos].astype(np.int32)
        self.h_ins_o_pos = o_[order_pos].astype(np.int32)
        # tombstones sorted by (p, s, o) enumerate base PSO positions in
        # ascending order; the POS positions need their own sort
        tomb = sorted(self._tomb_set)
        pos_ps = np.array([self._base_pos_ps(*t) for t in tomb], np.int32)
        pos_po = np.sort(np.array([self._base_pos_po(*t) for t in tomb],
                                  np.int32))
        t = pos_ps.shape[0]
        self.h_tomb_pos_ps = pos_ps
        self.h_tomb_adj_ps = pos_ps - np.arange(t, dtype=np.int32)
        self.h_tomb_pos_po = pos_po
        self.h_tomb_adj_po = pos_po - np.arange(t, dtype=np.int32)
        self.n_triples = self.n_base - t + int(ins.shape[0])

    def merged_triples(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The logical triple set as ``(s, p, o)`` int64 arrays:
        base minus tombstones plus inserts (what ``TripleStore.build``
        of it would index — the byte-identity reference)."""
        p_all = (self.h_key_ps // self.n_terms).astype(np.int64)
        s_all = self.h_s_pso.astype(np.int64)
        o_all = self.h_o_pso.astype(np.int64)
        live = np.ones(self.n_base, bool)
        live[self.h_tomb_pos_ps] = False
        ins = np.array(sorted(self._ins_set), np.int64).reshape(-1, 3)
        return (np.concatenate([s_all[live], ins[:, 1]]),
                np.concatenate([p_all[live], ins[:, 0]]),
                np.concatenate([o_all[live], ins[:, 2]]))

    def compact(self) -> int:
        """Fold the delta into the base: one full re-sort of the logical
        triple set, off the serving path (in-flight waves keep their old
        device view — the upload swap is atomic at the epoch bump).

        Logical content is unchanged, so the bump logs an *empty*
        touched-predicate set: every cache/planner entry carries over.
        Returns the new epoch (unchanged when the delta is empty).
        """
        if not self._ins_set and not self._tomb_set:
            return self.epoch
        s, p, o = self.merged_triples()
        rebuilt = TripleStore.build(s, p, o, n_terms=self.n_terms,
                                    n_predicates=self.n_predicates)
        for f in ("h_key_ps", "h_s_pso", "h_o_pso", "h_key_po", "h_s_pos",
                  "h_o_pos", "h_pred_offsets"):
            setattr(self, f, getattr(rebuilt, f))
        self.n_base = rebuilt.n_triples
        self._ins_set = set()
        self._tomb_set = set()
        self._rebuild_delta()
        assert self.n_triples == rebuilt.n_triples
        self.base_epoch += 1
        return self._bump(frozenset(), delta_only=False)

    def maybe_compact(self, frac: float = 0.25, floor: int = 0) -> bool:
        """Compact when the delta crossed ``max(frac * n_base, floor)``
        — the size/cost threshold of the periodic compaction policy.
        Returns True when a compaction ran."""
        if self.delta_size == 0:
            return False
        if self.delta_size < max(frac * self.n_base, floor, 1):
            return False
        self.compact()
        return True

    # ------------------------------------------------- host planning helpers
    def pred_run(self, p: int) -> tuple[int, int]:
        """Run [lo, hi) of predicate ``p`` in *base* PSO (== POS) order."""
        return int(self.h_pred_offsets[p]), int(self.h_pred_offsets[p + 1])

    def ps_run(self, p: int, s: int) -> tuple[int, int]:
        """Run [lo, hi) of (p, s, ?o) *base* rows in PSO order."""
        key = self.key(p, s)
        lo = int(np.searchsorted(self.h_key_ps, key, side="left"))
        hi = int(np.searchsorted(self.h_key_ps, key, side="right"))
        return lo, hi

    def po_run(self, p: int, o: int) -> tuple[int, int]:
        """Run [lo, hi) of (?s, p, o) *base* rows in POS order."""
        key = self.key(p, o)
        lo = int(np.searchsorted(self.h_key_po, key, side="left"))
        hi = int(np.searchsorted(self.h_key_po, key, side="right"))
        return lo, hi

    def _tombs_in(self, pos: np.ndarray, lo: int, hi: int) -> int:
        return int(np.searchsorted(pos, hi, side="left")
                   - np.searchsorted(pos, lo, side="left"))

    def _ins_count_ps(self, key_lo: int, key_hi: int) -> int:
        return int(np.searchsorted(self.h_ins_key_ps, key_hi, side="left")
                   - np.searchsorted(self.h_ins_key_ps, key_lo, side="left"))

    def tp_cardinality(self, p: int, s: int | None = None, o: int | None = None) -> int:
        """Exact *logical* cardinality of a bound-predicate triple pattern
        (base minus tombstones plus inserts — what a rebuilt store would
        report, so plan ordering matches it bit-for-bit).

        This is the Def. 6 ``void:triples`` metadata value (here exact,
        i.e. the F-specific threshold eps = 0).
        """
        if s is not None and o is not None:
            lo, hi = self.ps_run(p, s)
            base = int(np.searchsorted(self.h_o_pso[lo:hi], o, side="right")
                       - np.searchsorted(self.h_o_pso[lo:hi], o, side="left"))
            if not self._ins_set and not self._tomb_set:
                return base
            t = (int(p), int(s), int(o))
            return base - (t in self._tomb_set) + (t in self._ins_set)
        if s is not None:
            lo, hi = self.ps_run(p, s)
            return (hi - lo) - self._tombs_in(self.h_tomb_pos_ps, lo, hi) \
                + self._ins_count_ps(self.key(p, s), self.key(p, s + 1))
        if o is not None:
            lo, hi = self.po_run(p, o)
            ins = int(np.searchsorted(self.h_ins_key_po, self.key(p, o + 1),
                                      "left")
                      - np.searchsorted(self.h_ins_key_po, self.key(p, o),
                                        "left"))
            return (hi - lo) - self._tombs_in(self.h_tomb_pos_po, lo, hi) \
                + ins
        lo, hi = self.pred_run(p)
        return (hi - lo) - self._tombs_in(self.h_tomb_pos_ps, lo, hi) \
            + self._ins_count_ps(self.key(p, 0), self.key(p + 1, 0))

    def max_ins_degrees(self) -> tuple[np.ndarray, np.ndarray]:
        """Per-predicate max insert-run lengths, (p,s)-keyed and
        (p,o)-keyed — the delta term of the capacity planner's degree
        oracle (merged max degree <= base max + insert max, since
        tombstones only shrink runs).  Delta-sized host work."""
        out_ps = np.zeros(self.n_predicates + 1, np.int64)
        out_po = np.zeros(self.n_predicates + 1, np.int64)
        for keys, out in ((self.h_ins_key_ps, out_ps),
                          (self.h_ins_key_po, out_po)):
            if keys.shape[0]:
                uniq, counts = np.unique(keys, return_counts=True)
                np.maximum.at(out, (uniq // self.n_terms).astype(np.int64),
                              counts)
        return out_ps, out_po

    # --------------------------------------------------------------- sharding
    def shard_by_subject(self, n_shards: int) -> list["TripleStore"]:
        """Hash-partition by subject; pad shards to equal base count.

        Padding triples use predicate id ``n_predicates`` (one past the
        last real predicate) so they can never match a query pattern, and
        sort to the end of every index.  The *base* partitions are cached
        per ``base_epoch``; a delta-only epoch just redistributes the
        (small) delta onto the cached shards — the sharded lowering never
        pays a per-write re-shard.
        """
        cached = self._shard_cache.get(n_shards)
        if cached is None:
            # reconstruct (s, p, o) from the base PSO arrays
            p_all = (self.h_key_ps // self.n_terms).astype(np.int64)
            s_all = self.h_s_pso.astype(np.int64)
            o_all = self.h_o_pso.astype(np.int64)
            shard_of = _subject_hash(s_all) % n_shards
            counts = np.bincount(shard_of, minlength=n_shards)
            cap = int(counts.max()) if n_shards > 0 else 0
            shards: list[TripleStore] = []
            for i in range(n_shards):
                m = shard_of == i
                pad = cap - int(m.sum())
                # padding triples carry the out-of-range predicate and
                # distinct subjects (so the build-time dedup keeps all)
                s_i = np.concatenate([s_all[m],
                                      np.arange(pad, dtype=np.int64)])
                p_i = np.concatenate([p_all[m],
                                      np.full(pad, self.n_predicates,
                                              np.int64)])
                o_i = np.concatenate([o_all[m], np.zeros(pad, np.int64)])
                shards.append(
                    TripleStore.build(
                        s_i, p_i, o_i,
                        n_terms=self.n_terms,
                        n_predicates=self.n_predicates,  # padding pred is out of range by design
                    )
                )
            cached = [shards, -1]
            self._shard_cache[n_shards] = cached
        shards, applied = cached
        if applied != self.epoch:
            for i, shard in enumerate(shards):
                shard._ins_set = {t for t in self._ins_set
                                  if _owner(t[1], n_shards) == i}
                shard._tomb_set = {t for t in self._tomb_set
                                   if _owner(t[1], n_shards) == i}
                shard._rebuild_delta()
                shard.epoch = self.epoch
                shard._device = None  # delta-only: shard._dev_base is kept
            cached[1] = self.epoch
        return shards

    def stacked_shard_arrays(self, n_shards: int) -> StoreArrays:
        """Shard and stack device arrays along a leading shard axis.

        Output arrays have shape ``[n_shards, cap]`` — the layout consumed
        by ``shard_map`` in the distributed engine.  Delta arrays are
        padded to a common (pow2) length across shards with the same
        padding values as the single-store device view.
        """
        shards = self.shard_by_subject(n_shards)
        m_pad = max((s.h_ins_key_ps.shape[0] for s in shards), default=0)
        t_pad = max((s.h_tomb_pos_ps.shape[0] for s in shards), default=0)
        # same stable-bucket policy as the single-store device view, with
        # the floor scaled to the (largest) shard's base length
        floor = max(1, max((s.n_base for s in shards), default=1) // 4)
        m_pad = _pow2(max(m_pad, floor)) if m_pad else 0
        t_pad = _pow2(max(t_pad, floor)) if t_pad else 0
        deltas = [s._delta_host_padded(m_pad, t_pad) for s in shards]
        base = [jnp.stack([getattr(s.device, f) for s in shards])
                for f in StoreArrays._fields[:6]]
        delta = [jnp.stack([jnp.asarray(d[i]) for d in deltas])
                 for i in range(10)]
        return StoreArrays(*base, *delta)


def check_key_radix(n_terms: int, n_predicates: int) -> None:
    """Refuse a dictionary whose composite keys would not fit int32.

    Keys are ``p * n_terms + term`` for predicate ids up to
    ``n_predicates`` (the shard padding predicate), so the largest key or
    probe bound is below ``(n_predicates + 1) * n_terms``; it must stay
    below ``KEY_LIMIT``, which is also the key padding value.
    """
    bound = (int(n_predicates) + 1) * int(n_terms)
    if bound >= KEY_LIMIT:
        raise ValueError(
            f"composite key radix overflow: (n_predicates + 1) * n_terms = "
            f"({n_predicates} + 1) * {n_terms} = {bound} does not fit the "
            f"int32 keys the probe kernels take (limit {KEY_LIMIT}); "
            f"shard the dictionary")


def _owner(s: int, n_shards: int) -> int:
    return int(_subject_hash(np.array([s], np.int64))[0]) % n_shards


def _subject_hash(s: np.ndarray) -> np.ndarray:
    """Deterministic 64-bit mix (splitmix64 finaliser) for subject sharding."""
    x = s.astype(np.uint64)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    x = x ^ (x >> np.uint64(31))
    return (x & np.uint64(0x7FFFFFFFFFFFFFFF)).astype(np.int64)
