"""Static-shape solution-mapping tables and ragged expansion.

JAX needs static shapes, so a set of solution mappings (the paper's Omega /
intermediate results) is a fixed-capacity table:

    rows    int32[cap, n_vars]   (-1 = unbound)
    valid   bool[cap]            valid rows form a prefix (tables are kept
                                 compacted after filtering steps)
    overflow bool                capacity was exceeded somewhere upstream —
                                 the analogue of the paper's 10-min timeout.

The search primitives the tables are joined with (``eqrange``,
``run_probe`` / ``run_contains``) live in the backend-dispatched kernel
layer ``repro.kernels.ops`` — Pallas on TPU, jnp oracles elsewhere.  This
module keeps only the table machinery itself:

- ``expand``: given per-row runs ``[lo_i, hi_i)``, enumerate (row, element)
  pairs into a fresh table of capacity ``cap`` via cumsum + searchsorted —
  the standard prefix-sum trick for ragged expansion under static shapes.
  Its internal ``searchsorted`` over the cumulative-degree vector routes
  through ``kops.searchsorted`` like every other rank primitive, so the
  Pallas column-stream probe covers it on TPU at large capacities
  (ROADMAP follow-up from the dispatch-layer refactor).
- ``compact`` / ``set_column``: table maintenance.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.kernels import ops as kops


UNBOUND = jnp.int32(-1)


class BindingTable(NamedTuple):
    rows: jnp.ndarray  # int32[cap, n_vars]
    valid: jnp.ndarray  # bool[cap]
    overflow: jnp.ndarray  # bool scalar

    @property
    def cap(self) -> int:
        return self.rows.shape[0]

    @property
    def n_vars(self) -> int:
        return self.rows.shape[1]

    def count(self) -> jnp.ndarray:
        return jnp.sum(self.valid.astype(jnp.int64))


def unit_table(cap: int, n_vars: int) -> BindingTable:
    """Table with a single all-unbound row — the evaluation seed (Omega with
    the empty mapping), matching Def. 5's empty-Omega case."""
    rows = jnp.full((cap, n_vars), UNBOUND, dtype=jnp.int32)
    valid = jnp.zeros((cap,), dtype=bool).at[0].set(True)
    return BindingTable(rows, valid, jnp.asarray(False))


# --------------------------------------------------------------------------
# ragged expansion
# --------------------------------------------------------------------------

class Expansion(NamedTuple):
    src_row: jnp.ndarray  # int32[cap]   source row index per output row
    flat_idx: jnp.ndarray  # int32[cap]  absolute index into the store array
    valid: jnp.ndarray  # bool[cap]
    total: jnp.ndarray  # int64 scalar: true (unclamped) number of outputs


def source_rows(cum: jnp.ndarray, cap: int) -> jnp.ndarray:
    """Source row of each output slot ``j < cap`` of a ragged expansion:
    ``searchsorted(cum, j, side="right")`` over the cumulative degrees.

    The search runs in int32, which is what the probe kernels take:
    clamping ``cum`` at ``cap`` changes no answer for ``j < cap`` and
    keeps the int64 running total (the overflow signal) out of it.
    """
    cum32 = jnp.minimum(cum, cap).astype(jnp.int32)
    return kops.searchsorted(cum32, jnp.arange(cap, dtype=jnp.int32),
                             side="right")


def expand(lo: jnp.ndarray, hi: jnp.ndarray, row_valid: jnp.ndarray,
           cap: int) -> Expansion:
    """Enumerate (row, run element) pairs for per-row runs ``[lo_i, hi_i)``.

    Output row ``j`` draws from source row ``src = searchsorted(cumdeg, j)``
    at offset ``j - cumdeg[src-1]``.  Rows with ``row_valid=False`` contribute
    degree 0.  Output valid rows form a prefix by construction.
    """
    deg = jnp.where(row_valid, (hi - lo).astype(jnp.int64), 0)
    cum = jnp.cumsum(deg)
    total = cum[-1]
    starts = cum - deg
    j = jnp.arange(cap, dtype=jnp.int64)
    src = source_rows(cum, cap)
    src_c = jnp.clip(src, 0, lo.shape[0] - 1)
    r = j - starts[src_c]
    flat = lo[src_c].astype(jnp.int64) + r
    valid = j < total
    flat = jnp.where(valid, flat, 0)
    return Expansion(
        src_row=src_c.astype(jnp.int32),
        flat_idx=flat.astype(jnp.int64),
        valid=valid,
        total=total,
    )


def compact(table: BindingTable) -> BindingTable:
    """Stable-partition valid rows to a prefix.

    The permutation ``argsort(~valid, stable=True)`` would give, built
    from two running counts instead of a sort: the TPU compiler spends
    ~13 s on each stable sort of a binding table, and every filtering
    unit step holds one.
    """
    valid = table.valid
    v = valid.astype(jnp.int32)
    n_valid = jnp.sum(v, dtype=jnp.int32)
    dest = jnp.where(valid, jnp.cumsum(v, dtype=jnp.int32) - 1,
                     n_valid + jnp.cumsum(1 - v, dtype=jnp.int32) - 1)
    order = jnp.zeros_like(dest).at[dest].set(
        jnp.arange(dest.shape[0], dtype=jnp.int32), unique_indices=True)
    return BindingTable(table.rows[order], valid[order], table.overflow)


def set_column(rows: jnp.ndarray, col: int, values: jnp.ndarray) -> jnp.ndarray:
    return rows.at[:, col].set(values.astype(jnp.int32))
