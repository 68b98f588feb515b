"""A whole run of each cell on the CPU, through the function ``run.py``
calls, with the Pallas kernels in interpret mode: traffic, warm-up,
window, reference check and the result object.  Only the look for a chip
is skipped, and the graph and the instances are small."""

import json
import time

import pytest

import harness
from faults import FAULTS

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
