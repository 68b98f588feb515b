"""Pallas TPU kernel: on-device fingerprint of a binding-table block.

The concurrent scheduler keys its star-fragment cache on the canonical
seeded unit request, which embeds the Omega block — the valid prefix of the
wave's binding table restricted to the unit's read columns.  PR 2/3 pulled
that block to the host on *every unit step of every wave* just to call
``tobytes()`` (the ROADMAP round-trip item); at scheduler capacities this is
megabytes of PCIe traffic per step for what ends up a dict key.

This kernel hashes the block where it lives: one pass over the table tile
stream computes a 4x32-bit order-sensitive digest of the valid prefix, and
only the 16-byte digest crosses to the host.  The hash spec (and its
constants) is defined in ``repro.kernels.ref`` and shared by three
implementations that must stay bit-identical:

- ``ref.fingerprint_rows_ref``   — jnp oracle (non-TPU dispatch target),
- ``fingerprint_rows_pallas``    — this kernel (TPU fast path),
- ``ref.fingerprint_prefix_np``  — numpy twin for host-replayed wave state.

TPU adaptation: the block streams through VMEM *transposed* — a
``(C, R_TILE)`` tile, so each column is a lane-dense row and every per-row
hash step is a plain elementwise op on ``(1, R_TILE)`` vectors.  Per tile
the VPU computes each row's column-folded hash, mixes in the global row
position, masks invalid rows, and adds four salted per-lane partials into
a ``(4, R_TILE)`` output block across grid steps (init at tile 0).  The
wrapper sums the lanes and finalizes (n-mix) outside the kernel, so the
oracle and kernel share the exact same tail arithmetic.

The kernel computes in **int32** with logical shifts: Mosaic refuses
reductions over unsigned integers and every 64-bit type, while int32
wrap-around multiply and add give the same bits as the uint32 spec — the
wrapper bitcasts the partials back to uint32 before the lane sum.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import ZERO
from repro.kernels.ref import (_FP_COL, _FP_POS, _FP_SALTS, _FP_SEED, _M32,
                               _mix32_jnp)

DEFAULT_R_TILE = 1024


def _i32(c: int) -> int:
    """A uint32 constant as the int32 with the same bits (a Python int, so
    it stays a weak-typed literal inside the kernel)."""
    c &= _M32
    return c - (1 << 32) if c >= 1 << 31 else c


def _mix32_i32(x):
    """``ref._mix32_jnp`` on int32 bit patterns (logical shifts)."""
    def shr(v, k):
        return jax.lax.shift_right_logical(v, jnp.full_like(v, k))

    x = x ^ shr(x, 16)
    x = x * _i32(0x85EBCA6B)
    x = x ^ shr(x, 13)
    x = x * _i32(0xC2B2AE35)
    x = x ^ shr(x, 16)
    return x


def _fp_kernel(block_ref, mask_ref, acc_ref, *, n_cols: int):
    i = pl.program_id(0)
    r_tile = mask_ref.shape[1]
    h = jnp.full((1, r_tile), _i32(_FP_SEED), jnp.int32)
    for c in range(n_cols):  # static unroll: n_cols is a trace constant
        v = block_ref[c:c + 1, :]  # [1, R_TILE]: column c of the rows
        h = _mix32_i32(h ^ (v + _i32((c + 1) * _FP_COL)))
    # global row index per lane (2D iota: TPU rejects 1D)
    local = jax.lax.broadcasted_iota(jnp.int32, (1, r_tile), 1)
    pos = (i * r_tile + local + 1) * _i32(_FP_POS)
    g = _mix32_i32(h ^ _mix32_i32(pos))
    m = mask_ref[...]
    partial = jnp.concatenate(
        [_mix32_i32(g + _i32(s)) * m for s in _FP_SALTS], axis=0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = partial

    @pl.when(i != 0)
    def _accum():
        acc_ref[...] = acc_ref[...] + partial


@functools.partial(jax.jit, static_argnames=("r_tile", "interpret"))
def fingerprint_rows_pallas(block: jnp.ndarray, valid: jnp.ndarray,
                            r_tile: int = DEFAULT_R_TILE,
                            interpret: bool = False) -> jnp.ndarray:
    """uint32[4] digest of the valid rows of ``block`` (int32[n, C], C >= 1).

    ``valid`` masks rows; in the engine it is always a prefix (tables stay
    compacted), but the kernel only requires a mask.  Row padding to the
    tile multiple carries ``valid=False`` and contributes nothing, so the
    digest is independent of the table capacity — only the valid rows,
    their positions and their count matter (the contract the scheduler's
    host/device key parity rests on).
    """
    n, n_cols = block.shape
    if n_cols == 0:
        raise ValueError("fingerprint_rows_pallas needs >= 1 column; "
                         "the dispatch layer routes 0-column blocks to ref")
    r_pad = -n % r_tile
    block_t = jnp.pad(block.astype(jnp.int32), ((0, r_pad), (0, 0))).T
    mask_p = jnp.pad(valid.astype(jnp.int32), (0, r_pad)).reshape(1, -1)
    grid = (block_t.shape[1] // r_tile,)
    acc = pl.pallas_call(
        functools.partial(_fp_kernel, n_cols=n_cols),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_cols, r_tile), lambda i: (ZERO, i)),
            pl.BlockSpec((1, r_tile), lambda i: (ZERO, i)),
        ],
        out_specs=pl.BlockSpec((4, r_tile), lambda i: (ZERO, ZERO)),
        out_shape=jax.ShapeDtypeStruct((4, r_tile), jnp.int32),
        interpret=interpret,
        name="fingerprint",
    )(block_t, mask_p)
    acc = jnp.sum(jax.lax.bitcast_convert_type(acc, jnp.uint32), axis=1,
                  dtype=jnp.uint32)
    # shared finalize (identical to the oracle's tail)
    n_in = jnp.sum(valid.astype(jnp.uint32), dtype=jnp.uint32)
    salts = jnp.asarray(_FP_SALTS, jnp.uint32)
    return _mix32_jnp(acc ^ (n_in * jnp.uint32(_FP_POS) + salts))
