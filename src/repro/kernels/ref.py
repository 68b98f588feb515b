"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

The join-primitive oracles (``eqrange_ref``, ``run_probe_ref``, ...) double
as the engine's non-TPU execution path: ``repro.kernels.ops`` dispatches to
them whenever the Pallas kernels don't apply.  They are shard_map/vmap-safe
(pure jnp, fixed iteration counts, no data-dependent shapes).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def sorted_probe_ref(keys: jnp.ndarray, queries: jnp.ndarray
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """rank = searchsorted-left; contains = membership (keys sorted asc)."""
    rank = jnp.searchsorted(keys, queries, side="left").astype(jnp.int32)
    n = keys.shape[0]
    at = keys[jnp.clip(rank, 0, n - 1)]
    contains = (rank < n) & (at == queries)
    return rank, contains


def eqrange_ref(sorted_keys: jnp.ndarray, query_keys: jnp.ndarray
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-query equal range ``[lo, hi)`` in a globally sorted key array."""
    lo = jnp.searchsorted(sorted_keys, query_keys, side="left")
    hi = jnp.searchsorted(sorted_keys, query_keys, side="right")
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def delta_probe_ref(ins_keys: jnp.ndarray, tomb_pos: jnp.ndarray,
                    query_keys: jnp.ndarray, base_lo: jnp.ndarray,
                    base_hi: jnp.ndarray
                    ) -> tuple[jnp.ndarray, jnp.ndarray,
                               jnp.ndarray, jnp.ndarray]:
    """The merged base+delta probe's delta half, in pure jnp.

    Insert-key equal range of each ``query_keys[i]`` plus the tombstone
    ranks of the base run bounds ``base_lo[i]``/``base_hi[i]`` (count of
    tombstoned base positions strictly below each) — the jnp oracle for
    ``delta_probe_pallas``.
    """
    ins_lo, ins_hi = eqrange_ref(ins_keys, query_keys)
    tomb_lo = jnp.searchsorted(tomb_pos, base_lo, side="left")
    tomb_hi = jnp.searchsorted(tomb_pos, base_hi, side="left")
    return (ins_lo, ins_hi,
            tomb_lo.astype(jnp.int32), tomb_hi.astype(jnp.int32))


def delta_probe_np(ins_keys: "np.ndarray", tomb_pos: "np.ndarray",
                   query_keys: "np.ndarray", base_lo: "np.ndarray",
                   base_hi: "np.ndarray") -> tuple:
    """Host (numpy) twin of ``delta_probe_ref`` — bit-identical outputs;
    the three-way parity partner the kernel tests pin alongside the
    Pallas and jnp paths (like ``fingerprint_prefix_np``)."""
    ins_lo = np.searchsorted(ins_keys, query_keys, side="left")
    ins_hi = np.searchsorted(ins_keys, query_keys, side="right")
    tomb_lo = np.searchsorted(tomb_pos, base_lo, side="left")
    tomb_hi = np.searchsorted(tomb_pos, base_hi, side="left")
    return (ins_lo.astype(np.int32), ins_hi.astype(np.int32),
            tomb_lo.astype(np.int32), tomb_hi.astype(np.int32))


def rank_ref(sorted_keys: jnp.ndarray, queries: jnp.ndarray,
             side: str = "left") -> jnp.ndarray:
    """One-sided rank (``searchsorted``) of ``queries`` in a sorted array.

    The scan lowering, behind an optimization barrier: when ``queries`` is
    a compile-time constant (the arange of ``bindings.source_rows``, this
    oracle's main caller) XLA would otherwise try to constant-fold the
    search.  ``method="sort"`` avoids that too, but the TPU compiler spends
    ~30 s on each such sort.
    """
    return jnp.searchsorted(sorted_keys,
                            jax.lax.optimization_barrier(queries),
                            side=side, method="scan").astype(jnp.int32)


def subject_shard_ref(subjects: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    """Owning shard of each subject id: splitmix64 finaliser mod ``n_shards``.

    Must match ``rdf.store._subject_hash`` — the host-side partitioner the
    distributed store was built with.
    """
    x = subjects.astype(jnp.uint64)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    x = x ^ (x >> jnp.uint64(31))
    return ((x & jnp.uint64(0x7FFFFFFFFFFFFFFF)).astype(jnp.int64)
            % n_shards).astype(jnp.int32)


def searchsorted_in_runs_ref(values: jnp.ndarray, lo: jnp.ndarray,
                             hi: jnp.ndarray, targets: jnp.ndarray,
                             side: str = "left") -> jnp.ndarray:
    """Binary search of ``targets[i]`` within ``values[lo[i]:hi[i]]`` (each run
    individually sorted).  Returns absolute insertion positions.

    Pure bisection with a fixed iteration count (static shapes); this is the
    jnp oracle for the Pallas ``run_probe`` kernel's rank output.
    """
    n = values.shape[0]
    steps = max(1, math.ceil(math.log2(max(n, 2))) + 1)

    def body(_, state):
        lo_, hi_ = state
        mid = (lo_ + hi_) >> 1
        v = values[jnp.clip(mid, 0, n - 1)]
        if side == "left":
            go_right = v < targets
        else:
            go_right = v <= targets
        lo_ = jnp.where(go_right & (lo_ < hi_), mid + 1, lo_)
        hi_ = jnp.where((~go_right) & (lo_ < hi_), mid, hi_)
        return lo_, hi_

    lo_f, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo_f


def run_probe_ref(values: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                  targets: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(pos, contains) of ``targets[i]`` within the sorted run
    ``values[lo[i]:hi[i]]`` — the fused oracle for ``run_probe_pallas``."""
    pos = searchsorted_in_runs_ref(values, lo, hi, targets, side="left")
    n = values.shape[0]
    at = values[jnp.clip(pos, 0, n - 1)]
    contains = (pos < hi) & (at == targets)
    return pos.astype(jnp.int32), contains


def max_run_length_per_segment_ref(sorted_keys: jnp.ndarray,
                                   segment_ids: jnp.ndarray,
                                   num_segments: int) -> jnp.ndarray:
    """Per-segment maximum equal-key run length in a sorted key column.

    ``sorted_keys`` ascending; ``segment_ids`` non-decreasing (a run never
    crosses a segment boundary — predicate runs in the PSO/POS layouts
    guarantee this).  Returns int64[num_segments]; empty segments get 0.
    A few vectorized reductions (change-point cumsum + two segment ops) —
    this is the capacity planner's degree oracle, computed once per store
    epoch, so it has no Pallas fast path by design.
    """
    n = sorted_keys.shape[0]
    if n == 0:
        return jnp.zeros((num_segments,), jnp.int64)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_keys[1:] != sorted_keys[:-1]])
    run_id = jnp.cumsum(is_start.astype(jnp.int64)) - 1
    run_len = jax.ops.segment_sum(jnp.ones((n,), jnp.int64), run_id,
                                  num_segments=n)
    out = jax.ops.segment_max(run_len[run_id], segment_ids,
                              num_segments=num_segments)
    return jnp.maximum(out, 0)


# --------------------------------------------------------------------------
# request fingerprints (the scheduler's digest-first cache keys)
# --------------------------------------------------------------------------
#
# The hash is defined entirely in wrapping uint32 arithmetic so that the
# jnp oracle, the Pallas kernel and the numpy host twin produce identical
# bit patterns (the scheduler mixes device-resident and host-replayed wave
# state, and both must canonicalize to the same cache key).  Layout:
#
#     h_i   = fold over columns c of mix32(h ^ (v[i, c] + (c+1)*COL))
#     g_i   = mix32(h_i ^ mix32((i+1) * POS))        position-dependent
#     acc_s = sum_{valid i} mix32(g_i + SALT_s)       (mod 2^32, s = 0..3)
#     out_s = mix32(acc_s ^ (n_valid * POS + SALT_s))
#
# Only the valid prefix contributes (invalid rows are masked to zero), so
# the digest of a device table whose invalid region holds step garbage
# equals the digest of its host-side valid-prefix materialisation.

_M32 = 0xFFFFFFFF
_FP_SEED = 0x9E3779B9
_FP_COL = 0x85EBCA6B
_FP_POS = 0x9E3779B1
_FP_SALTS = (0x243F6A88, 0x85A308D3, 0x13198A2E, 0x03707344)


def _mix32_jnp(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 finalizer on uint32 (wrapping)."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    return x


def fingerprint_rows_ref(block: jnp.ndarray, valid: jnp.ndarray
                         ) -> jnp.ndarray:
    """uint32[4] digest of the valid rows of ``block`` (int32[n, C]).

    Pure jnp oracle for ``fingerprint_rows_pallas``; vmap/shard_map-safe
    (static column unroll, masked sum).  Must stay bit-identical to
    ``fingerprint_prefix_np`` on prefix-valid inputs.
    """
    n, n_cols = block.shape
    h = jnp.full((n,), _FP_SEED, jnp.uint32)
    for c in range(n_cols):
        v = block[:, c].astype(jnp.uint32)
        h = _mix32_jnp(h ^ (v + jnp.uint32(((c + 1) * _FP_COL) & _M32)))
    pos = (jnp.arange(n, dtype=jnp.uint32) + jnp.uint32(1)) \
        * jnp.uint32(_FP_POS)
    g = _mix32_jnp(h ^ _mix32_jnp(pos))
    m = valid.astype(jnp.uint32)
    n_in = jnp.sum(m, dtype=jnp.uint32)
    outs = []
    for s in _FP_SALTS:
        acc = jnp.sum(_mix32_jnp(g + jnp.uint32(s)) * m, dtype=jnp.uint32)
        outs.append(_mix32_jnp(
            acc ^ (n_in * jnp.uint32(_FP_POS) + jnp.uint32(s))))
    return jnp.stack(outs)


def _mix32_np(x: "np.ndarray") -> "np.ndarray":
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    x = x ^ (x >> np.uint32(16))
    return x


def fingerprint_prefix_np(block: "np.ndarray") -> tuple[int, int, int, int]:
    """Host twin of ``fingerprint_rows_ref`` for an all-valid prefix block.

    ``block`` is the valid prefix ``int32[n, C]`` (every row valid, in
    order).  Bit-identical to the device digest of a cap-sized table whose
    valid prefix is exactly ``block`` — pinned by the kernel parity tests.
    """
    block = np.ascontiguousarray(block, dtype=np.int32)
    n, n_cols = block.shape
    h = np.full((n,), _FP_SEED, np.uint32)
    for c in range(n_cols):
        v = block[:, c].astype(np.uint32)
        h = _mix32_np(h ^ (v + np.uint32(((c + 1) * _FP_COL) & _M32)))
    pos = (np.arange(n, dtype=np.uint32) + np.uint32(1)) * np.uint32(_FP_POS)
    g = _mix32_np(h ^ _mix32_np(pos))
    accs = np.array(
        [np.sum(_mix32_np(g + np.uint32(s)), dtype=np.uint32) if n else 0
         for s in _FP_SALTS], np.uint32)
    fins = np.array([(n * _FP_POS + s) & _M32 for s in _FP_SALTS], np.uint32)
    # 1-D arrays throughout: numpy warns on (harmless, intended) uint32
    # wrap-around for scalar/0-d operands but not for arrays
    out = _mix32_np(accs ^ fins)
    return tuple(int(x) for x in out)


# --------------------------------------------------------------------------
# fragment replay (the scheduler's device-side cache-hit path)
# --------------------------------------------------------------------------

def replay_delta_ref(seed_rows: jnp.ndarray, src: jnp.ndarray,
                     written: jnp.ndarray, n_out: jnp.ndarray,
                     write_cols: tuple[int, ...]
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter a cached fragment delta onto a lane's seed prefix, on device.

    ``seed_rows`` is the lane's full-capacity table ``int32[cap, n_vars]``
    whose valid prefix is the unit's input Omega block; ``src`` the delta's
    source-row indices ``int32[M]`` (entries past ``n_out`` are padding),
    ``written`` the values for the unit's write columns ``int32[M, W]``,
    ``n_out`` the true output row count (traced scalar).  Returns the
    replayed ``(rows, valid)`` at full capacity with the invalid region
    UNBOUND-filled — the device twin of ``fragcache.replay`` (bit-identical
    on the valid prefix; pinned by the kernel parity tests).  vmap-safe:
    the scheduler replays whole waves in one call.
    """
    cap, n_vars = seed_rows.shape
    m = src.shape[0]
    live = jnp.arange(m, dtype=jnp.int32) < n_out
    take = jnp.where(live, src, 0)
    out = seed_rows[take]  # [M, n_vars]
    for w, c in enumerate(write_cols):
        out = out.at[:, c].set(written[:, w])
    out = jnp.where(live[:, None], out, jnp.int32(-1))
    rows = jnp.full((cap, n_vars), -1, jnp.int32).at[:m].set(out)
    valid = jnp.arange(cap, dtype=jnp.int32) < n_out
    return rows, valid


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True, scale: float | None = None
                  ) -> jnp.ndarray:
    """Dense reference attention with GQA head-group broadcast.

    q [B, Hq, Sq, D]; k, v [B, Hkv, Sk, D] -> [B, Hq, Sq, D].
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * scale
    if causal:
        qi = jnp.arange(sq)[:, None]
        kj = jnp.arange(sk)[None, :]
        s = jnp.where(qi >= kj, s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      causal: bool = True, scale: float | None = None,
                      block_k: int = 1024) -> jnp.ndarray:
    """Flash-structured attention in pure jnp: online softmax over KV
    blocks via lax.scan, never materialising the [Sq, Sk] score matrix.

    This mirrors the Pallas kernel's IO behaviour exactly, which matters
    for the dry-run: lowering the dense reference would charge the roofline
    with O(S^2) bytes and spurious gathers that the TPU kernel never pays.
    Numerics are identical to ``attention_ref`` (same math, blocked).
    """
    import jax

    b, hq, sq, dh = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    pad = -sk % block_k
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nblk = kp.shape[2] // block_k
    qg = q.reshape(b, hkv, group, sq, dh).astype(jnp.float32)
    kb = kp.reshape(b, hkv, nblk, block_k, dh).transpose(2, 0, 1, 3, 4)
    vb = vp.reshape(b, hkv, nblk, block_k, dh).transpose(2, 0, 1, 3, 4)

    def body(carry, inp):
        m, l, acc, j = carry[0], carry[1], carry[2], carry[3]
        kj, vj = inp
        s = jnp.einsum("bkgqd,bksd->bkgqs", qg,
                       kj.astype(jnp.float32)) * scale
        kpos = j * block_k + jnp.arange(block_k)
        mask = kpos[None, None, None, None, :] < sk
        if causal:
            qpos = jnp.arange(sq)
            mask = mask & (qpos[None, None, None, :, None]
                           >= kpos[None, None, None, None, :])
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bksd->bkgqd", p, vj.astype(jnp.float32))
        return (m_new, l, acc, j + 1), None

    m0 = jnp.full((b, hkv, group, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, group, sq, dh), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, a0, jnp.int32(0)),
                                     (kb, vb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, hq, sq, dh).astype(q.dtype)


def embedding_bag_ref(table: jnp.ndarray, ids: jnp.ndarray,
                      mode: str = "sum") -> jnp.ndarray:
    """EmbeddingBag oracle: table [V, D], ids [B, F] -> [B, D] (sum/mean)."""
    emb = jnp.take(table, ids, axis=0)  # [B, F, D]
    out = jnp.sum(emb, axis=1)
    if mode == "mean":
        out = out / ids.shape[1]
    return out
