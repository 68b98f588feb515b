"""Ahead-of-time compiles of each cell's unit-step programs at the cell's
real sizes, for a described v5e chip (no chip needed).

The plans come from a small graph of the same schema: plan structure
(units, branch cases, columns) depends on the predicates' relative
cardinalities, which every scale shares.  Each program is lowered with
the 10M-triple store's array shapes, the cell's wave width and table
capacity, and compiled by the TPU compiler, which refuses what the chip
would refuse.  The topology is described in a fixture, never at import.
"""

import os

import jax
import jax.numpy as jnp
import pytest

import harness
import traffic as tf
import watdiv

CELLS = ("spf-2stars-cold", "brtpf-2stars-cold")
LANES = 8  # the closed loop's waves: 32 clients, 8 per template


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def unit_plans(name):
    """Each template's plan units, planned on a small graph."""
    from repro.core.engine import EngineConfig, plan_query
    from repro.endpoint.parse import parse_select
    from repro.rdf.store import TripleStore

    cell = harness.load_cell(name)
    g = watdiv.generate(watdiv.GraphConfig(scale=100), 1)
    store = TripleStore.build(g.s, g.p, g.o, n_terms=g.n_terms,
                              n_predicates=g.n_predicates)
    insts = tf.Instances(g, cell.mix["templates"],
                         int(cell.config["fit_rows"]), 1, reserve=1)
    ecfg = EngineConfig(**cell.config["engine"])
    plans = []
    for pool in insts.warmup():
        plans.append(plan_query(store, parse_select(pool[0].text).bgp, ecfg))
    return cell, ecfg, plans


@pytest.mark.parametrize("name", CELLS)
def test_unit_steps_compile_for_v5e(chip, name, monkeypatch):
    from repro.core import stepper
    from repro.core.server import log_factor
    from repro.kernels import ops
    from repro.rdf.store import StoreArrays

    # JAX's default backend here is the CPU: make the dispatch layer trace
    # the Mosaic kernels, as it does on the chip
    monkeypatch.setattr(ops, "FORCE", "pallas")
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    cell, ecfg, plans = unit_plans(name)
    n = int(cell.config["triples"])
    gcfg = watdiv.GraphConfig.from_json(cell.config)
    radix = watdiv.layout(gcfg)[-1]
    i32 = jnp.int32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    dev = StoreArrays(*([sds((n,), i32)] * 6), *([sds((0,), i32)] * 10))
    cap = ecfg.cap
    compiled = 0
    for plan in plans:
        v = max(plan.n_vars, 1)
        for up in plan.units:
            step = stepper.unit_step(up, radix, logn=log_factor(n))
            exe = step.lower(dev, sds((LANES, len(plan.consts)), jnp.int64),
                             sds((LANES, cap, v), i32),
                             sds((LANES, cap), jnp.bool_),
                             sds((LANES,), jnp.bool_)).compile()
            assert "tpu_custom_call" in exe.as_text()
            compiled += 1
    assert compiled == sum(len(p.units) for p in plans)
