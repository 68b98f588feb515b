"""A whole run of each cell on the CPU, through the function ``run.py``
calls, with the Pallas kernels in interpret mode: traffic, warm-up,
window, reference check and the result object.  Only the look for a chip
is skipped, and the graph and the instances are small."""

import json
import time
from pathlib import Path

import pytest

import harness
import traffic as tf
from faults import FAULTS

DATA = Path(__file__).resolve().parent / "data"
CELLS = ("spf-2stars-cold", "brtpf-2stars-cold")
SMOKE_SCALE = 20  # the repository's SMOKE_GRAPH scale


def small(name):
    cell = harness.load_cell(name)
    cell.mix.update(clients=len(cell.mix["templates"]))  # waves of one
    cell.config["fit_rows"] = 8
    return cell


def run(name, fault=None, seed=5):
    from repro.kernels import ops

    old, ops.FORCE = ops.FORCE, "pallas"
    try:
        return harness.run_cell(small(name), seed, 1.0, False,
                                t_start=time.perf_counter(),
                                scale=SMOKE_SCALE, wait_s=120.0,
                                fault=fault)
    finally:
        ops.FORCE = old


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name):
    out = run(name)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert out["correct"] is True
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"]
                                   for m in harness.load_cell(name).end_to_end}
    assert {"p50_s", "setup_s"} <= set(out["metrics"])
    assert all(m["value"] > 0 for k, m in out["metrics"].items()
               if k != "qpm")
    assert out["compared"]["wrong_answers"] == {"value": 0, "limit": 0}
    assert out["compared"]["unanswered"] == {"value": 0, "limit": 0}
    json.dumps(out)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(fault):
    out = run("spf-2stars-cold", fault=FAULTS[fault])
    assert out["correct"] is False
    c = out["compared"]
    assert c["wrong_answers"]["value"] + c["unanswered"]["value"] >= 1


def run_union(seed, seconds, scale, fit_rows, clients):
    """A run of the union test mix; returns the result and the log."""
    from repro.kernels import ops

    cell = small("spf-2stars-cold")
    cell.mix = tf.load_mix(DATA / "union-test.json")
    cell.mix.update(clients=clients)
    cell.config["fit_rows"] = fit_rows
    logs = []
    old, ops.FORCE = ops.FORCE, "pallas"
    try:
        out = harness.run_cell(cell, seed, seconds, False,
                               t_start=time.perf_counter(), scale=scale,
                               wait_s=300.0,
                               log=lambda **kw: logs.append(kw))
    finally:
        ops.FORCE = old
    return out, {x["phase"]: x for x in logs}


# The program compiles a device replay of cached fragments for every
# power-of-two replay width, which the warm-up cannot cover from the
# traffic (ROADMAP B7): that, and only that, may compile in a window.
REPLAY = {"jit(wave_replay)"}


def test_union_mix_runs_and_is_correct():
    """A mix of the union load's four shapes, 1-star, 2-stars, 3-stars
    and paths, through the same run, in waves of one: every answer
    matches the reference, and no unit step compiles in the window.
    Different instances can share a fragment here (a 3-stars chain
    reaches the same retailer), so replays run."""
    out, log = run_union(9, 1.0, SMOKE_SCALE, 8, 4)
    assert out["correct"] is True
    assert out["attempted"] >= 4 and out["failed"] == 0
    done = log["done"]
    assert set(done["compiled_in_window"]) <= REPLAY
    assert done["compiles_in_window"]["backend_compiles"] \
        == len(done["compiled_in_window"])


def test_a_table_past_cap_compiles_in_the_warm_up():
    """Where an instance's bound passes the engine's starting capacity
    (1,024 rows; here one 2-stars instance of 1,137 rows at this graph
    and cut), the warm-up serves it in a drain of its own, and the
    window, with two requests of each template in a drain, compiles no
    unit step: neither at the larger capacity nor at the widths of the
    drain that the warm-up shares with the window."""
    out, log = run_union(9, 5.0, 100, 2048, 8)
    assert log["warmup"]["past_cap"] == 1
    assert out["correct"] is True
    assert out["attempted"] >= 16 and out["failed"] == 0
    assert set(log["done"]["compiled_in_window"]) <= REPLAY


def test_warm_up_serves_the_past_cap_instances_after_the_drain():
    """The warm-up's first drain is the window's shape, with none of the
    instances past ``cap``; they follow, each once, in a drain of their
    own."""
    import watdiv
    from types import SimpleNamespace

    cell = small("spf-2stars-cold")
    cell.mix = tf.load_mix(DATA / "union-test.json")
    cell.mix.update(clients=8)
    g = watdiv.generate(watdiv.GraphConfig(scale=100), 9)
    insts = tf.Instances(g, cell.mix["templates"], 2048, 9, reserve=2,
                         cap=1024)
    big = [i.text for i in insts.past_cap()]
    assert len(big) == 1
    drains = []

    class Service:
        def serve(self, reqs):
            drains.append([r.sparql for r in reqs])
            return [SimpleNamespace(status="ok") for _ in reqs]

    harness.warm_up(cell, Service, insts, lambda **kw: None)
    pools = insts.warmup()
    assert drains == [[pools[i % 4][i // 4].text for i in range(8)], big]


def test_pool_running_out_is_a_bench_error():
    """Clients that ask for more instances than the pool holds end the
    run with the harness's own error, whichever client asks first."""
    from repro.kernels import ops

    cell = small("spf-2stars-cold")
    cell.mix.update(clients=32)
    cell.config["fit_rows"] = 2
    old, ops.FORCE = ops.FORCE, "pallas"
    try:
        with pytest.raises(harness.BenchError, match="pool ran out"):
            harness.run_cell(cell, 5, 30.0, False,
                             t_start=time.perf_counter(),
                             scale=SMOKE_SCALE, wait_s=120.0)
    finally:
        ops.FORCE = old
