"""The backend-dispatched kernel layer: every hot primitive, one call-site.

This module is the single seam between the engine and the hardware.  The
query engine (``core/server.py``), the distributed runtime
(``core/distributed.py`` — the primitives are shard_map/vmap-traced there),
the benchmarks, and the model stacks all route their perf-critical
primitives through these wrappers; nothing above this layer mentions
Pallas or picks a backend.

Dispatch policy
---------------
The Pallas kernels target TPU.  Each wrapper picks the Pallas path when the
default JAX backend is TPU and the pure-jnp oracle (``repro.kernels.ref``)
elsewhere, so CPU tests and benches stay fast while the TPU lowering is
exercised by ``tests/test_chip_compile.py`` (AOT compiles for a described
v5e chip) and ``chip_smoke.py`` (on the chip).  On a non-TPU backend a
forced Pallas path runs in ``interpret=True`` mode (kernel-body semantics,
no Mosaic); on a TPU a kernel never runs interpreted.  Every kernel takes
int32 operands on the served path — Mosaic has no 64-bit vectors.

Set ``repro.kernels.ops.FORCE`` to ``"pallas"`` / ``"ref"`` to override:

- tests use ``FORCE="pallas"`` (+ interpret on CPU) to validate kernel
  bodies and engine-level byte-parity against ``FORCE="ref"``;
- benches use it to measure both paths on the same host.

``FORCE`` is read at *trace* time: jitted engine functions bake the chosen
path in, so flip it before building an engine (or clear the engine's jit
cache), not mid-run.

The SPF primitives additionally ride a per-op circuit breaker
(``BREAKER``, a :class:`KernelBreaker`): a Pallas path that faults at
trace time falls back to the byte-identical jnp oracle for that call,
repeated faults open the breaker (oracle-only until a half-open probe
recovers), and ``BREAKER.generation`` is folded into the stepper's jit
-cache keys so transitions retrace compiled steps.  Results never change
— the two paths are bit-exact twins — only throughput degrades.

Join/probe primitives (the SPF server's hot path)
-------------------------------------------------
- ``eqrange``             — per-query equal range in a sorted key column;
                            Pallas path: one fused ``sorted_probe`` pass
                            emitting both rank sides.
- ``run_probe``           — rank + membership of targets within per-row
                            sorted runs; Pallas path: the scalar-prefetch
                            windowed ``run_probe`` kernel (per-row-block
                            ``min(lo)/max(hi)`` tile windows — value tiles
                            no row in the block touches never stream from
                            HBM), with the dense full-column kernel kept
                            behind ``PROBE_VARIANT = "dense"``.
- ``run_contains``        — membership-only view of ``run_probe``.
- ``searchsorted_in_runs`` — rank-only view of ``run_probe``; also the
                            per-column primitive under the k-way shard
                            merge (``stepper.merge_sorted_blocks`` ranks
                            pre-sorted blocks into each other through this
                            seam, so the distributed gather-merge rides
                            the same backend dispatch).
- ``sorted_probe``        — rank-left + membership in one sorted array.
- ``delta_probe``         — the merged base+delta probe's delta half:
                            insert-key equal range + tombstone ranks of
                            the base run bounds, one fused pass (Pallas
                            kernel, jnp oracle, numpy twin — three-way
                            parity-pinned like ``replay_delta``).
- ``searchsorted``        — one-sided rank in one sorted array (the ragged
                            expansion's cumulative-degree bookkeeping in
                            ``core/bindings.py`` routes through this).
- ``eqrange_owned``       — ``eqrange`` fused with subject-ownership
                            masking (the distributed runtime's
                            ``owner_masking``): non-owned rows get an
                            empty run instead of a separate mask pass.
                            Pallas path: the ``owned_probe`` kernel with
                            the subject hash *inside* the tile loop
                            (32-bit-limb splitmix64 on the VPU) — non-
                            owned rows short-circuit to the empty run in
                            kernel, no post-hoc mask.
- ``fingerprint_rows``    — 4x32-bit on-device digest of a binding-table
                            block's valid rows (the scheduler's
                            digest-first fragment-cache keys; host twin
                            ``ref.fingerprint_prefix_np``).
- ``replay_delta``        — device-side fragment replay: scatter a cached
                            delta onto a lane's seed prefix in place
                            (Pallas broadcast-compare gather; numpy twin
                            ``fragcache.replay``), so all-hit scheduler
                            waves never materialise Omega blocks on host.
- ``max_run_length_per_segment`` — per-predicate max equal-key run length
                            (the capacity planner's degree oracle; jnp
                            segment ops on both backends — one-shot per
                            store epoch, no kernel needed).
- ``probe_op_cost``       — host-side cost model of one dispatched point
                            probe (the TPF page-accounting path charges
                            the *active* primitive, not an analytic logn).
"""

from __future__ import annotations

import logging
import math

import jax
import jax.numpy as jnp

from repro import faults, obs
from repro.kernels import ref
from repro.kernels.delta_probe import delta_probe_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.owned_probe import MAX_SHARDS, eqrange_owned_pallas
from repro.kernels.run_probe import (
    run_probe_pallas,
    run_probe_prefetch_pallas,
)
from repro.kernels.sorted_probe import sorted_probe_pallas

log = logging.getLogger(__name__)

FORCE: str | None = None  # None | "pallas" | "ref"

# which run_probe kernel the Pallas path dispatches: the scalar-prefetch
# windowed variant (default — skips value tiles outside each row block's
# touched window) or the dense full-column-stream kernel.  Read at trace
# time like FORCE: flip it before building an engine, not mid-run.
PROBE_VARIANT: str = "prefetch"  # "prefetch" | "dense"


class KernelBreaker:
    """Per-primitive circuit breaker over the Pallas dispatch.

    The graceful-degradation half of the failure plane: a primitive whose
    Pallas path keeps faulting (at trace time — these wrappers run when
    jit traces) is **opened** after ``threshold`` consecutive faults and
    served by the byte-identical jnp oracle instead, so a broken kernel
    degrades throughput, never availability or results.  After
    ``cooldown`` blocked calls the breaker goes **half-open**: the next
    call probes the Pallas path once — success closes the breaker,
    another fault re-opens it.  Each individual fault also falls back to
    the oracle for that call (the caller never sees the exception), so a
    *transient* fault below the threshold costs one slow call and
    nothing else.

    ``generation`` increments on every state transition and is folded
    into the stepper's jit-cache keys (like ``FORCE``), so compiled step
    functions that baked the old path are retraced after a transition —
    without it an open breaker would be invisible to already-compiled
    engines.  Transitions are mirrored as obs-gated
    ``kernels.breaker.<prim>.<state>`` instruments and tracer instants;
    the breaker itself always works, armed observability or not.

    The model-stack kernels (``attention``) are deliberately *not*
    guarded: their fallbacks are numerically close, not byte-identical,
    so a silent mid-run path swap could change model outputs.  Only the
    SPF probe/digest/replay primitives — whose two paths are bit-exact
    twins pinned by the parity tests — ride the breaker.
    """

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, threshold: int = 3, cooldown: int = 8):
        self.threshold = threshold  # consecutive faults that open
        self.cooldown = cooldown  # blocked calls before the half-open probe
        self.generation = 0
        self._state: dict[str, str] = {}
        self._consec: dict[str, int] = {}  # consecutive faults while closed
        self._blocked: dict[str, int] = {}  # oracle-served calls while open

    def state(self, prim: str) -> str:
        return self._state.get(prim, self.CLOSED)

    def snapshot(self) -> dict[str, str]:
        """Non-closed breaker states, {prim: state}."""
        return {p: s for p, s in self._state.items() if s != self.CLOSED}

    def reset(self) -> None:
        if self._state:
            self.generation += 1
        self._state.clear()
        self._consec.clear()
        self._blocked.clear()

    def _transition(self, prim: str, new: str) -> None:
        self._state[prim] = new
        self.generation += 1
        if obs.enabled:
            obs.registry.inc(f"kernels.breaker.{prim}.{new}")
            tr = obs.tracer
            if tr:
                tr.instant("kernel.breaker", prim=prim, state=new)

    def allow(self, prim: str) -> bool:
        """May this call try the Pallas path?  Open breakers count the
        blocked call; the ``cooldown``-th moves to half-open (the *next*
        call is the probe — this one still takes the oracle)."""
        st = self._state.get(prim, self.CLOSED)
        if st != self.OPEN:
            return True
        b = self._blocked.get(prim, 0) + 1
        self._blocked[prim] = b
        if b >= self.cooldown:
            self._blocked[prim] = 0
            self._transition(prim, self.HALF_OPEN)
        return False

    def record_fault(self, prim: str) -> None:
        st = self._state.get(prim, self.CLOSED)
        if st == self.HALF_OPEN:  # failed probe: straight back to open
            self._blocked[prim] = 0
            self._transition(prim, self.OPEN)
            return
        c = self._consec.get(prim, 0) + 1
        self._consec[prim] = c
        if st == self.CLOSED and c >= self.threshold:
            self._blocked[prim] = 0
            self._transition(prim, self.OPEN)

    def record_ok(self, prim: str) -> None:
        if self._state.get(prim, self.CLOSED) == self.HALF_OPEN:
            self._transition(prim, self.CLOSED)
        self._consec[prim] = 0


#: The process-wide breaker all guarded wrappers consult.  Tests swap or
#: ``reset()`` it; ``stepper`` folds ``BREAKER.generation`` into its jit
#: -cache keys so transitions force retraces.
BREAKER = KernelBreaker()


#: Primitives whose Pallas fault has already been logged (once each).
_LOGGED_FAULTS: set[str] = set()


def _guarded(prim: str, pallas_fn, ref_fn):
    """Run ``pallas_fn`` under the breaker, falling back to ``ref_fn``.

    The ``kernel`` fault seam fires *inside* the try: an injected kernel
    fault is indistinguishable from a real trace-time failure, so the
    chaos suite exercises exactly the production fallback path.

    A fallback is never silent: the first fault of each primitive is
    logged with its exception (a kernel the TPU compiler refuses would
    otherwise quietly become the jnp oracle), and every oracle-served
    call is counted as ``kernels.dispatch.<prim>.breaker_ref`` when obs
    is on — ``chip_smoke.py`` fails on any.
    """
    if not BREAKER.allow(prim):
        _note(prim, "breaker_ref")
        return ref_fn()
    try:
        if faults.plan is not None:
            faults.hit("kernel", prim=prim)
        out = pallas_fn()
    except Exception:
        if prim not in _LOGGED_FAULTS:
            _LOGGED_FAULTS.add(prim)
            log.warning("Pallas %s failed; serving the jnp oracle instead",
                        prim, exc_info=True)
        BREAKER.record_fault(prim)
        _note(prim, "breaker_ref")
        return ref_fn()
    BREAKER.record_ok(prim)
    return out


def _use_pallas() -> bool:
    if FORCE == "pallas":
        return True
    if FORCE == "ref":
        return False
    if FORCE is not None:
        raise ValueError(f"ops.FORCE must be None, 'pallas' or 'ref'; "
                         f"got {FORCE!r}")
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    # interpret-mode execution when forced onto a non-TPU backend
    return jax.default_backend() != "tpu"


def _note(prim: str, path: str) -> None:
    """Obs-gated dispatch note: which backend path ``prim`` picked.

    Dispatch happens at trace time (these wrappers run when jit traces,
    not per execution), so the counter in ``obs.registry`` counts
    *traces* per ``kernels.dispatch.<prim>.<path>`` and the tracer
    instant marks when a trace dispatched which kernel.  Compiles to a
    single attribute check when observability is off — no dict writes,
    no event objects.
    """
    if not obs.enabled:
        return
    obs.registry.inc(f"kernels.dispatch.{prim}.{path}")
    tr = obs.tracer
    if tr:
        tr.instant(f"kernel.{prim}", path=path)


# --------------------------------------------------------------------------
# join/probe primitives
# --------------------------------------------------------------------------

def sorted_probe(keys: jnp.ndarray, queries: jnp.ndarray
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(rank, contains) of each query in a sorted key array."""
    if _use_pallas():
        def _pl():
            _note("sorted_probe", "pallas")
            rank_lo, _, contains = sorted_probe_pallas(
                keys, queries, interpret=_interpret())
            return rank_lo, contains
        return _guarded("sorted_probe", _pl,
                        lambda: ref.sorted_probe_ref(keys, queries))
    _note("sorted_probe", "ref")
    return ref.sorted_probe_ref(keys, queries)


# Below this many queries the kernel's O(N) column stream cannot amortize
# against O(Q log N) scalar searches (the query tile is 256 wide either
# way); auto-dispatch on TPU uses the jnp path instead.  A hard
# ``FORCE="pallas"`` still always takes the kernel — that's how the tests
# exercise kernel bodies on tiny inputs.
MIN_PALLAS_QUERIES = 64


def eqrange(sorted_keys: jnp.ndarray, query_keys: jnp.ndarray
            ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-query equal range ``[lo, hi)`` in a globally sorted key array.

    Both backends return int32 positions (identical bit patterns), so
    engine results are byte-stable across ``FORCE`` settings.  Small query
    batches (e.g. the 2-element predicate-bound lookup of scan_ovar_free)
    stay on the scalar jnp path under auto-dispatch — see
    ``MIN_PALLAS_QUERIES``.
    """
    if _use_pallas() and (FORCE == "pallas"
                          or query_keys.shape[0] >= MIN_PALLAS_QUERIES):
        def _pl():
            _note("eqrange", "pallas")
            rank_lo, rank_hi, _ = sorted_probe_pallas(
                sorted_keys, query_keys, interpret=_interpret())
            return rank_lo, rank_hi
        return _guarded("eqrange", _pl,
                        lambda: ref.eqrange_ref(sorted_keys, query_keys))
    _note("eqrange", "ref")
    return ref.eqrange_ref(sorted_keys, query_keys)


def delta_probe(ins_keys: jnp.ndarray, tomb_pos: jnp.ndarray,
                query_keys: jnp.ndarray, base_lo: jnp.ndarray,
                base_hi: jnp.ndarray
                ) -> tuple[jnp.ndarray, jnp.ndarray,
                           jnp.ndarray, jnp.ndarray]:
    """The merged base+delta probe's delta half, in one fused pass.

    For every dispatched base ``eqrange`` the delta overlay needs four
    more ranks over two short sorted columns: the equal range of the same
    probe keys in the *insert* key column (``ins_lo``/``ins_hi``) and the
    tombstone ranks of the base run bounds (``tomb_lo``/``tomb_hi`` =
    tombstoned base positions strictly below ``base_lo``/``base_hi``).
    Together they give live run lengths (``(hi-lo) - (thi-tlo)``), live
    offsets, and the insert run to merge in — probe cost grows with the
    delta size, not the store size.  Pallas path: the fused
    ``delta_probe`` kernel (one launch, both columns on the same tile
    stream); oracle: ``ref.delta_probe_ref``; host twin:
    ``ref.delta_probe_np`` — three-way parity-pinned like
    ``replay_delta``.  Same small-batch auto-dispatch policy as
    ``eqrange``.
    """
    if _use_pallas() and (FORCE == "pallas"
                          or query_keys.shape[0] >= MIN_PALLAS_QUERIES):
        def _pl():
            _note("delta_probe", "pallas")
            return delta_probe_pallas(ins_keys, tomb_pos, query_keys,
                                      base_lo, base_hi,
                                      interpret=_interpret())
        return _guarded("delta_probe", _pl,
                        lambda: ref.delta_probe_ref(ins_keys, tomb_pos,
                                                    query_keys, base_lo,
                                                    base_hi))
    _note("delta_probe", "ref")
    return ref.delta_probe_ref(ins_keys, tomb_pos, query_keys, base_lo,
                               base_hi)


def searchsorted(sorted_keys: jnp.ndarray, queries: jnp.ndarray,
                 side: str = "left") -> jnp.ndarray:
    """One-sided rank of ``queries`` in a sorted array (int32 positions).

    This is the dispatch seam for every plain ``searchsorted`` above the
    kernel layer — notably the cumulative-degree search inside
    ``bindings.expand`` (ROADMAP open item).  The Pallas path reuses the
    fused ``sorted_probe`` column stream; small batches stay on the scalar
    jnp path under auto-dispatch (``MIN_PALLAS_QUERIES``), same policy as
    ``eqrange``.
    """
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    if _use_pallas() and (FORCE == "pallas"
                          or queries.shape[0] >= MIN_PALLAS_QUERIES):
        def _pl():
            _note("searchsorted", "pallas")
            rank_lo, rank_hi, _ = sorted_probe_pallas(
                sorted_keys, queries, interpret=_interpret())
            return rank_lo if side == "left" else rank_hi
        return _guarded("searchsorted", _pl,
                        lambda: ref.rank_ref(sorted_keys, queries, side=side))
    _note("searchsorted", "ref")
    return ref.rank_ref(sorted_keys, queries, side=side)


def eqrange_owned(sorted_keys: jnp.ndarray, query_keys: jnp.ndarray,
                  subjects: jnp.ndarray, my_shard: jnp.ndarray,
                  n_shards: int
                  ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``eqrange`` with subject-ownership masking folded into the probe.

    On a subject-hash-sharded store, a bound-subject row can only match on
    the shard its subject hashes to.  Rows whose subject is not owned by
    ``my_shard`` get an *empty* run ``[lo, lo)`` — downstream filters and
    ragged expansions then skip them with no separate mask pass over the
    binding table (this replaces the per-unit hash-and-mask the
    distributed lane evaluator used to do outside the kernel layer).

    Returns ``(lo, hi, owned)``; ``owned`` is exposed so cost accounting
    can count only the rows the local shard actually probed.  The Pallas
    path runs the ``owned_probe`` kernel — the subject hash lives *inside*
    the tile loop (32-bit-limb splitmix64, bit-exact vs the uint64
    reference) and non-owned rows accumulate the left rank on both sides,
    so the empty run falls out of the kernel with no mask pass.  Small
    batches and shard counts past the kernel's fold-mod bound stay on the
    jnp masking path (same auto-dispatch policy as ``eqrange``).
    """
    def _rf():
        owned = ref.subject_shard_ref(subjects, n_shards) == my_shard
        lo, hi = eqrange(sorted_keys, query_keys)
        return lo, jnp.where(owned, hi, lo), owned

    if _use_pallas() and n_shards <= MAX_SHARDS \
            and (FORCE == "pallas"
                 or query_keys.shape[0] >= MIN_PALLAS_QUERIES):
        def _pl():
            _note("eqrange_owned", "pallas")
            return eqrange_owned_pallas(sorted_keys, query_keys, subjects,
                                        my_shard, n_shards,
                                        interpret=_interpret())
        return _guarded("eqrange_owned", _pl, _rf)
    _note("eqrange_owned", "ref")
    return _rf()


def run_probe(values: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
              targets: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(pos, contains) of ``targets[i]`` within the sorted run
    ``values[lo[i]:hi[i]]``; ``pos`` is the absolute "left" insertion point.
    """
    if _use_pallas():
        # config validation stays outside the breaker guard: a bad
        # PROBE_VARIANT is a caller error, never a kernel fault to absorb
        if PROBE_VARIANT not in ("prefetch", "dense"):
            raise ValueError(f"ops.PROBE_VARIANT must be 'prefetch' or "
                             f"'dense'; got {PROBE_VARIANT!r}")

        def _pl():
            if PROBE_VARIANT == "prefetch":
                _note("run_probe", "prefetch")
                return run_probe_prefetch_pallas(values, lo, hi, targets,
                                                 interpret=_interpret())
            _note("run_probe", "dense")
            return run_probe_pallas(values, lo, hi, targets,
                                    interpret=_interpret())
        return _guarded("run_probe", _pl,
                        lambda: ref.run_probe_ref(values, lo, hi, targets))
    _note("run_probe", "ref")
    return ref.run_probe_ref(values, lo, hi, targets)


def run_contains(values: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                 targets: jnp.ndarray) -> jnp.ndarray:
    """Membership of ``targets[i]`` in the sorted run ``values[lo[i]:hi[i]]``."""
    return run_probe(values, lo, hi, targets)[1]


def searchsorted_in_runs(values: jnp.ndarray, lo: jnp.ndarray,
                         hi: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Absolute "left" insertion position of ``targets[i]`` within the
    sorted run ``values[lo[i]:hi[i]]``."""
    return run_probe(values, lo, hi, targets)[0]


def fingerprint_rows(block: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Order-sensitive uint32[4] digest of the valid rows of ``block``.

    ``block`` is int32[n, C] (a binding table restricted to a unit's read
    columns), ``valid`` the row mask (always a prefix in the engine).  The
    digest depends only on the valid rows' values, positions and count —
    never on capacity padding or invalid-row garbage — so it can stand in
    for the block's bytes in ``server.unit_digest_key`` and be compared
    against host-side state hashed with ``ref.fingerprint_prefix_np``
    (bit-identical by construction; pinned by the kernel parity tests).
    vmap-safe: the scheduler digests whole waves in one call.

    Zero-column blocks (a unit that reads nothing from Omega) carry no
    content beyond the row count and always take the jnp path.
    """
    if _use_pallas() and block.shape[1] > 0:
        def _pl():
            _note("fingerprint_rows", "pallas")
            from repro.kernels.fingerprint import fingerprint_rows_pallas
            return fingerprint_rows_pallas(block, valid,
                                           interpret=_interpret())
        return _guarded("fingerprint_rows", _pl,
                        lambda: ref.fingerprint_rows_ref(block, valid))
    _note("fingerprint_rows", "ref")
    return ref.fingerprint_rows_ref(block, valid)


def replay_delta(seed_rows: jnp.ndarray, src: jnp.ndarray,
                 written: jnp.ndarray, n_out: jnp.ndarray,
                 write_cols: tuple[int, ...] = ()
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter a cached fragment delta onto a lane's seed prefix, on device.

    ``seed_rows`` int32[cap, V] (valid prefix = the unit's input Omega
    block), ``src`` int32[M] source-row indices (entries past ``n_out``
    are padding), ``written`` int32[M, W] values for the static
    ``write_cols``, ``n_out`` the true output row count.  Returns the
    replayed full-capacity ``(rows, valid)`` — bit-identical on the valid
    prefix to the host twin ``fragcache.replay`` (pinned by the kernel
    parity tests).  vmap-safe: the scheduler replays whole waves at once.
    """
    if _use_pallas() and seed_rows.shape[1] > 0:
        def _pl():
            _note("replay_delta", "pallas")
            from repro.kernels.replay import replay_delta_pallas
            return replay_delta_pallas(seed_rows, src, written, n_out,
                                       write_cols=tuple(write_cols),
                                       interpret=_interpret())
        return _guarded("replay_delta", _pl,
                        lambda: ref.replay_delta_ref(seed_rows, src, written,
                                                     n_out, tuple(write_cols)))
    _note("replay_delta", "ref")
    return ref.replay_delta_ref(seed_rows, src, written, n_out,
                                tuple(write_cols))


def probe_op_cost(n: int) -> int:
    """Per-probe op count of the *dispatched* point-probe primitive against
    a sorted column of length ``n`` — the TPF page-accounting cost model.

    The TPF interface's server work is locating each instantiated fragment
    (one ``eqrange`` per request block); until PR 5 the engine charged an
    analytic ``2 * ceil(log2 n)`` for it regardless of backend.  This ties
    the charge to the primitive the dispatch layer actually runs:

    - jnp-oracle path: two-sided binary search — ``2 * ceil(log2 n)``
      dependent scalar steps (the historical analytic model, unchanged);
    - Pallas path: the fused ``sorted_probe`` kernel streams the column in
      ``DEFAULT_K_TILE``-wide tiles past each query tile and emits both
      rank sides in one pass — amortized ``ceil(n / K_TILE)`` tile passes
      per probe, no 2x.  How many ops one tile pass is worth comes from
      ``kernels.calibration``: the ``fig_kernels`` bench harness fits it
      from measured walls on real hardware and writes it to
      ``BENCH_kernels.json``; without an artifact the historical guess of
      1 applies, so a fresh checkout charges exactly what it always did.

    Host-side and read at plan/trace time like ``FORCE`` itself: engines
    bake it into jitted cost accounting, so flip ``FORCE`` before building
    an engine (or clear its jit cache), never mid-run.
    """
    if _use_pallas():
        from repro.kernels import calibration
        from repro.kernels.sorted_probe import DEFAULT_K_TILE
        passes = max(1, -(-int(n) // DEFAULT_K_TILE))
        return max(1, math.ceil(calibration.tile_pass_ops() * passes))
    return 2 * max(1, math.ceil(math.log2(max(int(n), 2))))


def max_run_length_per_segment(sorted_keys: jnp.ndarray,
                               segment_ids: jnp.ndarray,
                               num_segments: int) -> jnp.ndarray:
    """Per-segment max equal-key run length in a sorted key column.

    The capacity planner's degree oracle: over the PSO key column this is
    each predicate's max subject out-degree, over POS its max object
    in-degree.  Runs once per store epoch (a few vectorized segment
    reductions), so both backends use the jnp oracle — there is no hot
    path to accelerate.
    """
    return ref.max_run_length_per_segment_ref(sorted_keys, segment_ids,
                                              num_segments)


# --------------------------------------------------------------------------
# model-stack kernels
# --------------------------------------------------------------------------

def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = True, scale: float | None = None) -> jnp.ndarray:
    """Fused (flash) attention with GQA support.

    Non-TPU fallback: the flash-STRUCTURED chunked jnp computation for
    long sequences (same IO profile as the Pallas kernel — what the
    dry-run must lower), the simple dense reference for short ones.
    """
    if _use_pallas():
        return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                      interpret=_interpret())
    if k.shape[2] >= 2048:
        return ref.attention_chunked(q, k, v, causal=causal, scale=scale)
    return ref.attention_ref(q, k, v, causal=causal, scale=scale)


def embedding_bag(table: jnp.ndarray, ids: jnp.ndarray,
                  mode: str = "sum") -> jnp.ndarray:
    """EmbeddingBag built from gather + reduce (JAX has no native one —
    this IS the system's embedding-lookup substrate, used by DeepFM and
    the SPF-backed feature store)."""
    return ref.embedding_bag_ref(table, ids, mode=mode)
