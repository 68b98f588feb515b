"""Ahead-of-time compiles of the served-path Pallas kernels for a v5e chip.

Interpret mode checks what a kernel computes, not whether Mosaic accepts
it: 64-bit vectors, bool outputs, unsigned reductions and unaligned 1-D
blocks all pass interpret mode and are refused by the TPU compiler.  Each
test here lowers one kernel at the served path's widths — an int32 key
column the length of the 10M-triple WatDiv store and a wave-sized query
batch — for a *described* v5e chip (no chip needed) and checks that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and each pytest-xdist
worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.delta_probe import delta_probe_pallas
from repro.kernels.fingerprint import fingerprint_rows_pallas
from repro.kernels.owned_probe import eqrange_owned_pallas
from repro.kernels.replay import replay_delta_pallas
from repro.kernels.run_probe import (run_probe_pallas,
                                     run_probe_prefetch_pallas)
from repro.kernels.sorted_probe import sorted_probe_pallas

N_KEYS = 10_000_000  # one key column of the 10M-triple store
DELTA = 1 << 22  # its delta bucket: pow2(n_base // 4)
LANES, CAP = 8, 1024  # one scheduler wave: lanes x table capacity
N_VARS, N_WRITE = 6, 2  # binding-table width, columns a unit writes
N_SHARDS = 4


@pytest.fixture(scope="module")
def topology():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topology):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topology.devices[0])


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _lanes(fn, *in_axes):
    """``fn`` vmapped over a wave's lanes, as the scheduler calls it."""
    return jax.vmap(fn, in_axes=in_axes)


@pytest.mark.parametrize("batched", [False, True], ids=["call", "wave"])
def test_sorted_probe_compiles(chip, batched):
    fn = lambda k, q: sorted_probe_pallas(k, q)  # noqa: E731
    q = (LANES, CAP) if batched else (LANES * CAP,)
    _assert_mosaic(_lanes(fn, None, 0) if batched else fn,
                   _shape(chip, (N_KEYS,)), _shape(chip, q))


@pytest.mark.parametrize("variant", ["dense", "prefetch"])
@pytest.mark.parametrize("batched", [False, True], ids=["call", "wave"])
def test_run_probe_compiles(chip, variant, batched):
    kernel = run_probe_pallas if variant == "dense" \
        else run_probe_prefetch_pallas
    fn = lambda v, lo, hi, t: kernel(v, lo, hi, t)  # noqa: E731
    r = (LANES, CAP) if batched else (LANES * CAP,)
    _assert_mosaic(_lanes(fn, None, 0, 0, 0) if batched else fn,
                   _shape(chip, (N_KEYS,)), _shape(chip, r),
                   _shape(chip, r), _shape(chip, r))


def test_delta_probe_compiles(chip):
    fn = lambda ins, tomb, k, lo, hi: delta_probe_pallas(  # noqa: E731
        ins, tomb, k, lo, hi)
    q = (LANES, CAP)
    _assert_mosaic(_lanes(fn, None, None, 0, 0, 0),
                   _shape(chip, (DELTA,)), _shape(chip, (DELTA,)),
                   _shape(chip, q), _shape(chip, q), _shape(chip, q))


def test_fingerprint_rows_compiles(chip):
    _assert_mosaic(_lanes(fingerprint_rows_pallas, 0, 0),
                   _shape(chip, (LANES, CAP, N_VARS)),
                   _shape(chip, (LANES, CAP), jnp.bool_))


def test_replay_delta_compiles(chip):
    cols = tuple(range(N_WRITE))
    fn = lambda r, s, w, n: replay_delta_pallas(  # noqa: E731
        r, s, w, n, write_cols=cols)
    _assert_mosaic(_lanes(fn, 0, 0, 0, 0),
                   _shape(chip, (LANES, CAP, N_VARS)),
                   _shape(chip, (LANES, CAP)),
                   _shape(chip, (LANES, CAP, N_WRITE)),
                   _shape(chip, (LANES,)))


def test_owned_probe_compiles(chip):
    fn = lambda k, q, s, me: eqrange_owned_pallas(  # noqa: E731
        k, q, s, me, N_SHARDS)
    _assert_mosaic(_lanes(fn, None, 0, 0, None),
                   _shape(chip, (N_KEYS // N_SHARDS,)),
                   _shape(chip, (LANES, CAP)), _shape(chip, (LANES, CAP)),
                   _shape(chip, ()))


@pytest.mark.parametrize("lowering", ["shard", "mesh"])
def test_wave_digest_and_replay_compile_on_a_mesh(topology, lowering,
                                                   monkeypatch):
    """A mesh wave's table comes back from its unit step spread over the
    mesh, and the scheduler digests and replays it there between unit
    steps.  XLA cannot partition a Mosaic kernel by itself, so both steps
    must run their kernels per device (``stepper._on_lanes``)."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import stepper
    from repro.kernels import ops

    monkeypatch.setattr(ops, "FORCE", "pallas")
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topology.devices).reshape(N_SHARDS, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    # the sharded lowering splits lanes along model and replicates them
    # along data; the replicated-store lowering splits them along both
    lane_axes = ("model",) if lowering == "shard" else ("data", "model")
    lanes = NamedSharding(mesh, P(lane_axes))
    rows = _shape(lanes, (LANES, CAP, N_VARS))
    digest = stepper.digest_step((0, 2), mesh, lane_axes)
    assert "tpu_custom_call" in digest.lower(
        rows, _shape(lanes, (LANES, CAP), jnp.bool_)).compile().as_text()
    replay = stepper.replay_step(tuple(range(N_WRITE)), mesh, lane_axes)
    assert "tpu_custom_call" in replay.lower(
        rows, _shape(lanes, (LANES, CAP)),
        _shape(lanes, (LANES, CAP, N_WRITE)),
        _shape(lanes, (LANES,))).compile().as_text()
