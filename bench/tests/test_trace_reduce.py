"""The trace reduction, on a small trace recorded on a TPU v5e.

``data/small.xplane.pb`` holds one ``bench.window`` span with three
``eqrange`` probe kernels (Pallas ``sorted_probe``, 8,192 queries over a
1M-key column), each followed by a 50 ms host sleep in a ``bench.sleep``
span.  The test recomputes busy time from the raw events on its own and
checks what the reduction makes of them: busy and window time, the
kernel map, the longest idle gaps and who they are put down to.
"""

from pathlib import Path

import numpy as np
import pytest

import trace_reduce as tr

TRACE = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def pd():
    return tr.load(TRACE)


def test_union_merges_overlaps():
    iv = np.array([[5, 7], [0, 2], [1, 3], [7, 8], [10, 11]], float)
    assert tr.union(iv).tolist() == [[0, 3], [5, 8], [10, 11]]
    assert tr.union(np.zeros((0, 2))).shape == (0, 2)


def test_short_names():
    assert tr.short("%vmap_jit_sorted_probe_pallas__.12 = (s32[8]) "
                    "custom-call(...)") == "vmap_jit_sorted_probe_pallas__"
    assert tr.short("fusion.3") == "fusion"


def test_reduce_matches_the_raw_events(pd):
    r = tr.reduce(pd)
    w0, w1 = tr.window_span(tr.host_spans(pd))
    (ops,) = tr.device_ops(pd)
    inside = [(s, s + d) for _, s, d in ops if s < w1 and s + d > w0]
    busy = tr.union(np.clip(np.asarray(inside, float), w0, w1))
    assert r["busy_s"] == pytest.approx(
        float((busy[:, 1] - busy[:, 0]).sum()) * 1e-9)
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    # three probes and three 50 ms sleeps: busy is a small part of it
    assert 0.15 < r["window_s"] < 1.0
    assert 0 < r["busy_s"] < r["window_s"] - 0.14


def test_kernel_map_finds_the_probes(pd):
    r = tr.reduce(pd)
    probe_ops = [s for name, s in r["breakdown"]["device_ops"]
                 if "sorted_probe" in name]
    assert probe_ops and r["kernel_s"]["probe"] == pytest.approx(
        sum(probe_ops))
    assert r["breakdown"]["device_ops"][0][0].find("sorted_probe") >= 0


def test_longest_gaps_are_the_sleeps(pd):
    gaps = tr.reduce(pd)["breakdown"]["idle_gaps"]
    assert len(gaps) <= tr.TOP
    top3 = gaps[:3]
    assert all(name == "bench.sleep" for name, _ in top3)
    assert all(0.045 < s < 0.2 for _, s in top3)
