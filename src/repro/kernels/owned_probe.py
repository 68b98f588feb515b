"""Pallas TPU kernel: sorted-key probe with in-kernel subject ownership.

The sharded lowering's unit evaluator (``core/server.py`` via
``kops.eqrange_owned``) probes every bound-subject row's key into the
sorted PS/PSO column, but on a subject-hash-sharded store only the shard
a row's subject hashes to can match it.  The pre-PR-6 dispatch masked
*around* the fused probe — every shard still streamed the full column
past every row, then zeroed the non-owned runs after the fact.

This kernel pushes the owner test into the tile loop: per query tile it
recomputes ``subject_shard(subjects) == my_shard`` (an in-register
splitmix64, ~20 VPU ops per lane — free next to the [Q_TILE x K_TILE]
compare) and short-circuits non-owned rows to the empty run by
accumulating the *left* partial rank into both outputs, so their final
``hi`` equals ``lo`` exactly — the same ``[lo, lo)`` contract as the
masking path, bit for bit.  Owned rows accumulate the usual
``(sum(lt), sum(le))`` pair of the fused ``sorted_probe`` kernel.

The hash itself is the 64-bit splitmix64 finalizer of
``ref.subject_shard_ref``, rebuilt from 32-bit limbs because the TPU VPU
has no 64-bit integer lanes: 32x32->64 multiplies via 16-bit halves,
shifts carried across the limb boundary, and the final ``mod n_shards``
folded limb-wise (``2**32 mod m`` is a trace-time constant; ``m <= 4096``
keeps the fold inside uint32).  Bit-exact against the uint64 reference
for int32 subjects (the store's term ids) including negatives — pinned by
the kernel parity tests.

``my_shard`` is a *traced* scalar (``jax.lax.axis_index`` under
shard_map), so it rides in as a scalar-prefetch operand
(``PrefetchScalarGridSpec``): resident in SMEM before the first tile,
readable at every grid step without a VMEM block of its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import ZERO, as_col, as_row
from repro.kernels.sorted_probe import DEFAULT_K_TILE, DEFAULT_Q_TILE

# the limb-wise fold of ``mod n_shards`` computes
# ``(hi % m) * (2**32 % m) + (lo % m)`` in uint32; ``m <= MAX_SHARDS``
# bounds that below ``m**2 + m < 2**32`` with room to spare
MAX_SHARDS = 4096


def _mul32(a, b):
    """Full 32x32 -> 64 multiply of uint32 arrays, via 16-bit halves.

    Returns ``(lo, hi)`` uint32 limbs.  Plain Python int constants only:
    ``jnp.uint32(...)`` scalars would be captured constants inside a
    Pallas kernel body (a trace error), while weak-typed ints promote
    cleanly against the uint32 operands.
    """
    a0, a1 = a & 0xFFFF, a >> 16
    b0, b1 = b & 0xFFFF, b >> 16
    ll = a0 * b0
    lh = a0 * b1
    hl = a1 * b0
    mid = (ll >> 16) + (lh & 0xFFFF) + (hl & 0xFFFF)
    lo = (ll & 0xFFFF) | ((mid & 0xFFFF) << 16)
    hi = a1 * b1 + (lh >> 16) + (hl >> 16) + (mid >> 16)
    return lo, hi


def _mul64(a_lo, a_hi, b_lo, b_hi):
    """Low 64 bits of a 64x64 multiply on (lo, hi) uint32 limb pairs."""
    lo, carry = _mul32(a_lo, b_lo)
    return lo, carry + a_lo * b_hi + a_hi * b_lo


def _xorshr(lo, hi, s):
    """``x ^= x >> s`` on limb pairs, for 0 < s < 32."""
    return lo ^ ((lo >> s) | (hi << (32 - s))), hi ^ (hi >> s)


def shard_of_limbs(s_lo, s_hi, n_shards: int):
    """splitmix64-based shard id from uint32 subject limbs.

    Bit-exact twin of ``ref.subject_shard_ref`` (same finalizer constants
    split into limbs, same bit-63 mask, ``mod n_shards`` folded limb-wise
    with the trace-time constant ``2**32 mod n_shards``).  Returns int32.
    """
    lo, hi = _xorshr(s_lo, s_hi, 30)
    lo, hi = _mul64(lo, hi, 0x1CE4E5B9, 0xBF58476D)
    lo, hi = _xorshr(lo, hi, 27)
    lo, hi = _mul64(lo, hi, 0x133111EB, 0x94D049BB)
    lo, hi = _xorshr(lo, hi, 31)
    hi = hi & 0x7FFFFFFF
    m = np.uint32(n_shards)
    r32 = np.uint32((1 << 32) % n_shards)
    # lax.rem, not ``%``: jnp's floor-mod wraps the remainder in int64
    # sign fix-ups under x64, which Mosaic cannot lower
    folded = jax.lax.rem(hi, m) * r32 + jax.lax.rem(lo, m)
    return jax.lax.rem(folded, m).astype(jnp.int32)


def _subject_limbs(subjects: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """uint32 ``(lo, hi)`` limbs of int32 ``subjects`` as the reference's
    uint64: ``lo`` is the bit pattern, ``hi`` the sign extension (all ones
    for a negative id) — no 64-bit op is emitted."""
    if subjects.dtype != jnp.int32:
        raise TypeError(f"owned_probe takes int32 subjects (the store's "
                        f"term ids), got {subjects.dtype}")
    lo = jax.lax.bitcast_convert_type(subjects, jnp.uint32)
    hi = jnp.where(subjects < 0, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    return lo, hi


def _owned_probe_kernel(shard_ref, s_lo_ref, s_hi_ref, keys_ref, queries_ref,
                        rank_lo_ref, rank_hi_ref, owned_ref, *,
                        n_shards: int):
    j = pl.program_id(1)
    keys = keys_ref[...]  # [1, K_TILE]
    qs = queries_ref[...]  # [Q_TILE, 1]
    owned = shard_of_limbs(s_lo_ref[...], s_hi_ref[...],
                           n_shards) == shard_ref[0]

    partial_lo = jnp.sum((keys < qs).astype(jnp.int32), axis=1,
                         keepdims=True, dtype=jnp.int32)
    # non-owned rows accumulate the LEFT rank on both sides: their final
    # hi lands exactly on lo — the empty run — with no post-pass mask
    partial_hi = jnp.where(
        owned, jnp.sum((keys <= qs).astype(jnp.int32), axis=1,
                       keepdims=True, dtype=jnp.int32), partial_lo)

    @pl.when(j == 0)
    def _init():
        rank_lo_ref[...] = partial_lo
        rank_hi_ref[...] = partial_hi
        owned_ref[...] = owned.astype(jnp.int32)

    @pl.when(j != 0)
    def _accum():
        rank_lo_ref[...] = rank_lo_ref[...] + partial_lo
        rank_hi_ref[...] = rank_hi_ref[...] + partial_hi


@functools.partial(jax.jit, static_argnames=("n_shards", "q_tile", "k_tile",
                                             "interpret"))
def eqrange_owned_pallas(keys: jnp.ndarray, query_keys: jnp.ndarray,
                         subjects: jnp.ndarray, my_shard: jnp.ndarray,
                         n_shards: int,
                         q_tile: int = DEFAULT_Q_TILE,
                         k_tile: int = DEFAULT_K_TILE,
                         interpret: bool = False
                         ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused ownership-masked equal-range probe; ``(lo, hi, owned)``.

    Same contract as the masking path in ``kops.eqrange_owned``: owned
    rows get the true equal range ``[lo, hi)`` of their key in the sorted
    column, non-owned rows the empty run ``[lo, lo)``.  ``my_shard`` may
    be traced (shard_map ``axis_index``); ``n_shards`` is static.

    Padding follows ``sorted_probe_pallas``: +max key/query padding, with
    ``rank_hi`` clamped to ``n`` after the fact so a query equal to the
    dtype max stays exact.  Subject padding is 0 — its ownership bit is
    arbitrary and sliced away with the query padding.
    """
    if not 1 <= n_shards <= MAX_SHARDS:
        raise ValueError(f"n_shards must be in [1, {MAX_SHARDS}], "
                         f"got {n_shards}")
    n = keys.shape[0]
    q = query_keys.shape[0]
    dt = jnp.promote_types(keys.dtype, query_keys.dtype)
    maxval = jnp.iinfo(dt).max
    keys_p = as_row(keys.astype(dt), k_tile, maxval)
    queries_p = as_col(query_keys.astype(dt), q_tile, maxval)
    s_lo, s_hi = _subject_limbs(subjects)
    s_lo = as_col(s_lo, q_tile, 0)
    s_hi = as_col(s_hi, q_tile, 0)
    shard = jnp.asarray(my_shard, jnp.int32).reshape((1,))
    q_len = queries_p.shape[0]

    col = pl.BlockSpec((q_tile, 1), lambda i, j, s: (i, ZERO))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q_len // q_tile, keys_p.shape[1] // k_tile),
        in_specs=[
            col,  # s_lo
            col,  # s_hi
            pl.BlockSpec((1, k_tile), lambda i, j, s: (ZERO, j)),  # keys
            col,  # queries
        ],
        out_specs=[col, col, col],
    )
    rank_lo, rank_hi, owned = pl.pallas_call(
        functools.partial(_owned_probe_kernel, n_shards=n_shards),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((q_len, 1), jnp.int32)] * 3,
        interpret=interpret,
        name="owned_probe",
    )(shard, s_lo, s_hi, keys_p, queries_p)
    rank_lo, rank_hi, owned = rank_lo[:q, 0], rank_hi[:q, 0], owned[:q, 0]
    rank_hi = jnp.minimum(rank_hi, n)
    return rank_lo, rank_hi, owned != 0
