"""Pallas TPU kernel: the merged base+delta probe's delta half.

With the delta overlay (``rdf.store``), every dispatched eqrange against a
base key column gains a second, delta-sized probe: the same equal range in
the sorted *insert* key column, plus the tombstone ranks of the base run
bounds (how many tombstoned base positions fall below ``lo`` / ``hi`` —
that pair turns the base run length into a *live* count and base offsets
into live offsets).  Four rank reductions over two short sorted columns,
for the same query batch the base probe just served.

Fusing them into one kernel pass matters for the same reason
``sorted_probe`` fuses both rank sides: the delta columns are tiny
(that's the point of a delta store), so the cost is dominated by getting
the query batch through the VPU, not by the column stream — one kernel
launch per dispatched probe keeps the delta overhead at
O(delta / K_TILE) tile passes instead of four separate launches.

Both delta columns stream through the same k-tile grid axis as
lane-dense ``(1, K_TILE)`` rows (padded to a common tiled length; on the
served path both are int32 — insert composite keys and base positions),
the per-query operands sit in ``(Q_TILE, 1)`` columns (``kernels.layout``),
and each query tile accumulates

    ins_lo[i]  = #{k in ins_keys  : k <  query_keys[i]}
    ins_hi[i]  = #{k in ins_keys  : k <= query_keys[i]}
    tomb_lo[i] = #{p in tomb_pos  : p <  base_lo[i]}
    tomb_hi[i] = #{p in tomb_pos  : p <  base_hi[i]}

across k-tile steps (init at j == 0), exactly the ``sorted_probe``
accumulation scheme.  Padding: insert keys pad with the key dtype's max
(invisible below it; the wrapper clamps ``ins_hi`` like ``sorted_probe``
does), tombstone positions pad with int32 max (base positions are always
``<= n_base < int32 max``, and both tombstone ranks use strict ``<``, so
the padding is never counted — no clamp needed).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import ZERO, as_col

DEFAULT_Q_TILE = 256
DEFAULT_K_TILE = 2048


def _count(col, qs, strict: bool):
    """int32 ``#{col < qs}`` (or ``<=``) per query row: [Q_TILE, 1]."""
    hit = (col < qs) if strict else (col <= qs)
    return jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True,
                   dtype=jnp.int32)


def _delta_probe_kernel(ins_ref, tomb_ref, qkey_ref, qlo_ref, qhi_ref,
                        ins_lo_ref, ins_hi_ref, tomb_lo_ref, tomb_hi_ref):
    j = pl.program_id(1)
    ins = ins_ref[...]  # [1, K_TILE] insert keys
    tomb = tomb_ref[...]  # [1, K_TILE] tombstoned base positions
    qk = qkey_ref[...]  # [Q_TILE, 1]
    ql = qlo_ref[...]
    qh = qhi_ref[...]
    partials = ((ins_lo_ref, _count(ins, qk, True)),
                (ins_hi_ref, _count(ins, qk, False)),
                (tomb_lo_ref, _count(tomb, ql, True)),
                (tomb_hi_ref, _count(tomb, qh, True)))

    @pl.when(j == 0)
    def _init():
        for ref, part in partials:
            ref[...] = part

    @pl.when(j != 0)
    def _accum():
        for ref, part in partials:
            ref[...] = ref[...] + part


@functools.partial(jax.jit, static_argnames=("q_tile", "k_tile", "interpret"))
def delta_probe_pallas(ins_keys: jnp.ndarray, tomb_pos: jnp.ndarray,
                       query_keys: jnp.ndarray, base_lo: jnp.ndarray,
                       base_hi: jnp.ndarray,
                       q_tile: int = DEFAULT_Q_TILE,
                       k_tile: int = DEFAULT_K_TILE,
                       interpret: bool = False
                       ) -> tuple[jnp.ndarray, jnp.ndarray,
                                  jnp.ndarray, jnp.ndarray]:
    """Fused delta probe: insert eqrange + tombstone ranks of the base run.

    ``ins_keys`` sorted insert composite keys, ``tomb_pos`` sorted int32
    tombstoned base positions, ``query_keys`` the probe keys the base
    eqrange just served, ``base_lo``/``base_hi`` that eqrange's result.
    Returns ``(ins_lo, ins_hi, tomb_lo, tomb_hi)`` int32 — see the module
    docstring for the definitions.  Keys of another dtype than int32
    (only the interpret-mode tests pass them) widen every lane to it.
    """
    m = ins_keys.shape[0]
    t = tomb_pos.shape[0]
    q = query_keys.shape[0]
    dt = jnp.promote_types(ins_keys.dtype, query_keys.dtype)
    maxkey = jnp.iinfo(dt).max
    k_len = max(m, t, 1)
    k_len += -k_len % k_tile
    # both columns pad to the common length k_len (an empty one included)
    ins_p = jnp.pad(ins_keys.astype(dt), (0, k_len - m),
                    constant_values=maxkey).reshape(1, -1)
    tomb_p = jnp.pad(tomb_pos, (0, k_len - t),
                     constant_values=jnp.iinfo(tomb_pos.dtype).max)
    tomb_p = tomb_p.astype(dt).reshape(1, -1)
    qk_p = as_col(query_keys.astype(dt), q_tile, maxkey)
    ql_p = as_col(base_lo.astype(dt), q_tile, 0)
    qh_p = as_col(base_hi.astype(dt), q_tile, 0)
    q_len = qk_p.shape[0]

    grid = (q_len // q_tile, k_len // k_tile)
    row = pl.BlockSpec((1, k_tile), lambda i, j: (ZERO, j))
    col = pl.BlockSpec((q_tile, 1), lambda i, j: (i, ZERO))
    out = pl.pallas_call(
        _delta_probe_kernel,
        grid=grid,
        in_specs=[row, row, col, col, col],
        out_specs=[col] * 4,
        out_shape=[jax.ShapeDtypeStruct((q_len, 1), jnp.int32)] * 4,
        interpret=interpret,
        name="delta_probe",
    )(ins_p, tomb_p, qk_p, ql_p, qh_p)
    ins_lo, ins_hi, tomb_lo, tomb_hi = (o[:q, 0] for o in out)
    # a query key equal to the dtype max sees the key padding in `<=`;
    # its true right-rank is m (same correction as sorted_probe)
    ins_hi = jnp.minimum(ins_hi, m)
    return ins_lo, ins_hi, tomb_lo, tomb_hi
