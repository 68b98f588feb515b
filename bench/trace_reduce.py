"""From a profiler trace to device busy time, idle gaps and kernel time.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
Each TPU is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per operation the device ran, with a start and a duration in
nanoseconds.  Host threads are planes of their own (``/host:CPU``), with
the host's trace events (``TraceAnnotation`` spans, JAX's dispatch).

- the window: the host span ``bench.window`` that the harness opens and
  closes at the window's edges; every device op is clipped to it;
- busy: the union of the device's op intervals, averaged over the chips;
- idle gaps: the spaces between busy intervals inside the traced window,
  each named by the shortest host event that covers half of it or more;
- kernel time: the summed durations of the ops whose names the kernel
  map (``kernels.json``) assigns to a kernel.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10
WINDOW = "bench.window"  # the harness's host span around the window


def load(path: Path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def find_xplane(tdir: Path) -> Path:
    files = sorted(Path(tdir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    return files[-1]


def short(name: str) -> str:
    """An op's name without its HLO text and instance number:
    ``%vmap_jit_sorted_probe_pallas__.12 = (...) custom-call(...)`` ->
    ``vmap_jit_sorted_probe_pallas__``."""
    return re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


def _events(line):
    return [(ev.name, ev.start_ns, ev.duration_ns) for ev in line.events]


def device_ops(pd) -> list[list[tuple[str, float, float]]]:
    """Per device plane, its ops as (short name, start_ns, duration_ns)."""
    out = []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops += [(short(n), s, d) for n, s, d in _events(line)]
        out.append(ops)
    return out


def host_spans(pd) -> list[tuple[str, float, float]]:
    """Host events (name, start_ns, duration_ns) from every host thread."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += _events(line)
    return out


def union(intervals: np.ndarray) -> np.ndarray:
    """Merged [start, end) intervals, sorted."""
    if intervals.shape[0] == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0])]
    merged = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return np.asarray(merged, np.float64)


def kernel_map() -> dict[str, list[str]]:
    return json.loads((BENCH / "kernels.json").read_text())["kernels"]


def kernel_of(op: str, kmap: dict[str, list[str]]) -> str | None:
    for kernel, patterns in kmap.items():
        if any(re.search(p, op) for p in patterns):
            return kernel
    return None


def _gap_label(e0: float, s1: float, spans) -> str:
    """What the host was doing while the device sat idle from ``e0`` to
    ``s1``: the shortest host span (the window's own aside) that covers
    at least half of the gap."""
    names, start, end = spans
    overlap = np.minimum(end, s1) - np.maximum(start, e0)
    ok = (overlap >= 0.5 * (s1 - e0)) & (names != WINDOW)
    if not ok.any():
        return "no host span"
    idx = np.nonzero(ok)[0]
    return str(names[idx[np.argmin((end - start)[idx])]])


def window_span(spans) -> tuple[float, float]:
    """The traced window: the host span named ``WINDOW`` that the harness
    opens when the window starts and closes when it ends."""
    for name, s, d in spans:
        if name == WINDOW:
            return float(s), float(s + d)
    raise ValueError(f"the trace holds no {WINDOW!r} span")


def reduce(pd) -> dict:
    """Busy time, kernel time and idle gaps inside the traced window."""
    per_dev = device_ops(pd)
    if not per_dev or not any(per_dev):
        raise ValueError("the trace holds no device operations")
    spans = host_spans(pd)
    w0, w1 = window_span(spans)
    kmap = kernel_map()
    busy, by_op, by_kernel, gaps = [], {}, {}, []
    for ops in per_dev:
        clipped = [(name, max(s, w0), min(s + d, w1)) for name, s, d in ops
                   if s < w1 and s + d > w0]
        iv = np.asarray([(s, e) for _, s, e in clipped],
                        np.float64).reshape(-1, 2)
        merged = union(iv)
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()) * 1e-9)
        for name, s, e in clipped:
            by_op[name] = by_op.get(name, 0.0) + (e - s) * 1e-9
            k = kernel_of(name, kmap)
            if k is not None:
                by_kernel[k] = by_kernel.get(k, 0.0) + (e - s) * 1e-9
        edges = np.concatenate([[w0], merged.ravel(), [w1]]).reshape(-1, 2)
        for e0, s1 in edges:
            if s1 > e0:
                gaps.append((s1 - e0, e0, s1))
    gaps.sort(reverse=True)
    arr = (np.asarray([n for n, _, _ in spans], dtype=object),
           np.asarray([s for _, s, _ in spans], np.float64),
           np.asarray([s + d for _, s, d in spans], np.float64))
    top_gaps = [[_gap_label(e0, s1, arr), float(g) * 1e-9]
                for g, e0, s1 in gaps[:TOP]]
    n_dev = len(per_dev)
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy) / n_dev, "window_s": (w1 - w0) * 1e-9,
            "kernel_s": {k: v / n_dev for k, v in by_kernel.items()},
            "breakdown": {"device_ops": [[k, v / n_dev] for k, v in top_ops],
                          "idle_gaps": top_gaps}}


def reduce_dir(tdir: Path) -> dict:
    return reduce(load(find_xplane(tdir)))
