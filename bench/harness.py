"""One run of one cell: set-up, warm-up, the measured window, the check.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own that this module finds by the name
``BENCHMARK.json`` gives it:

- ``BENCHMARK.json``'s ``configs[].file`` -> the configuration (graph,
  interface, engine and scheduler settings);
- ``traffic/<traffic>.json`` -> the traffic mix (templates, clients);
- ``metrics/<name>.py`` -> a reader ``read(window) -> float | None`` for
  each per-layer metric.

The program is the system under test: the harness builds its store from
the benchmark's own graph (``watdiv.py``), sends SPARQL text through
``EndpointService`` and reads its registry counters.  Traffic, timing,
the reduction of the profiler trace and the reference check all live
here, under ``bench/``.
"""

from __future__ import annotations

import asyncio
import gc
import importlib.util
import json
import math
import shutil
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

import traffic as tf
import watdiv
from reference import Reference, canonical

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / ".out"  # traces; listed in bench/.gitignore


class BenchError(Exception):
    """The run cannot be measured (no chip, bad cell, failed set-up)."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = tf.load_mix(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in moved
                              else [])]
    return Cell(name, int(w["chips"]), config, mix, e2e, layer)


# ----------------------------------------------------------------- requests
@dataclass
class Record:
    inst: tf.Instance
    sent: float  # perf_counter instant the request was sent
    done: float = math.nan
    status: str = "unanswered"
    rows: np.ndarray | None = None


@dataclass
class Window:
    """What a run measured; the per-layer readers take their numbers
    from here.  The requests due are those sent before the close; each
    is awaited after it, up to the run's wait."""

    cell: str
    seconds: float
    t0: float
    records: list[Record]
    snap: dict  # registry diff over the window (sched/cache/endpoint)
    compiles: dict  # CompileClock diff over the window
    trace: dict | None = None  # trace_reduce.reduce(...) of the window

    @property
    def close(self) -> float:
        return self.t0 + self.seconds

    def due(self) -> list[Record]:
        return [r for r in self.records if r.sent < self.close]

    def ok(self) -> list[Record]:
        return [r for r in self.due() if r.status == "ok"]

    def queries_per_s(self) -> float | None:
        """Requests due in the window and answered ``ok``, over the time
        from the window's open to the last of their answers: all the work
        sent in the window and all the time it took, so the rate moves
        with the server's speed and not in whole closed-loop drains."""
        ok = self.ok()
        if not ok:
            return None
        return len(ok) / (max(r.done for r in ok) - self.t0)

    def queries_answered(self) -> float:
        """Queries the window's rate answers in the window's length: the
        count the per-query readers divide by, over the same span as the
        window's counters and trace."""
        return (self.queries_per_s() or 0.0) * self.seconds

    def latencies(self) -> np.ndarray:
        """Per request due in the window: seconds from its send to its
        answer; ``inf`` where it was not answered ``ok``."""
        return np.asarray([r.done - r.sent if r.status == "ok" else math.inf
                           for r in self.due()], np.float64)


def quantile(values: np.ndarray, q: float) -> float:
    """Nearest-rank quantile (the value at rank ceil(q * n))."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return math.nan
    return float(v[max(int(math.ceil(q * v.size)) - 1, 0)])


class Marks:
    """The window's edges, taken on the event loop: the registry snapshot
    and compile counts at each edge, and the ``bench.window`` trace span
    between them (traced runs)."""

    def __init__(self, svc, clock, trace: bool):
        self.svc, self.clock, self.trace = svc, clock, trace
        self.snap = self.compiles = None
        self.programs: list[str] = []  # names of those compiled inside
        self._ann = None

    def _snapshot(self):
        # the drain thread may add an instrument while this one copies
        for _ in range(10):
            try:
                return self.svc.sched.snapshot()
            except RuntimeError:
                time.sleep(0.001)
        return self.svc.sched.snapshot()

    def open(self) -> float:
        self._before, self._c0 = self._snapshot(), self.clock.read()
        self._n0 = len(self.clock.programs)
        if self.trace:
            import jax

            from trace_reduce import WINDOW
            self._ann = jax.profiler.TraceAnnotation(WINDOW)
            self._ann.__enter__()
        return time.perf_counter()

    def close(self) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self.snap = self._snapshot() - self._before
        self.compiles = {k: v - self._c0[k]
                         for k, v in self.clock.read().items()}
        self.programs = self.clock.programs[self._n0:]


async def _closed_loop(svc, marks: Marks, stream, clients: int,
                       seconds: float, wait_s: float
                       ) -> tuple[float, list[Record]]:
    """``clients`` clients, one request in flight each, no think time,
    sending until the window closes; then the answers still due are
    awaited for up to ``wait_s``.  Raises ``BenchError`` where the
    traffic pool ran out."""
    runner = asyncio.ensure_future(svc.run())
    records: list[Record] = []
    exhausted: list[str] = []
    t0 = marks.open()
    close = t0 + seconds
    asyncio.get_running_loop().call_later(seconds, marks.close)

    async def client(c: int):
        while time.perf_counter() < close:
            try:
                inst = next(stream)
            except tf.PoolExhausted as e:
                exhausted.append(str(e))
                return
            except StopIteration:  # another client's next() exhausted it
                return
            rec = Record(inst, sent=time.perf_counter())
            records.append(rec)
            resp = await svc.submit(rec.inst.text, client=c)
            rec.done = time.perf_counter()
            rec.status, rec.rows = resp.status, resp.rows

    tasks = [asyncio.ensure_future(client(c)) for c in range(clients)]
    await asyncio.wait(tasks, timeout=seconds + wait_s)
    await _stop(tasks + [runner])
    if exhausted:
        raise BenchError(f"the traffic pool ran out inside the window: "
                         f"{exhausted[0]}; the server answers faster than "
                         f"this mix has distinct instances for")
    return t0, records


async def _stop(tasks) -> None:
    """Cancel what is still running; re-raise what a task raised."""
    for t in tasks:
        if not t.done():
            t.cancel()
    for r in await asyncio.gather(*tasks, return_exceptions=True):
        if isinstance(r, Exception):
            raise r


# -------------------------------------------------------------------- setup
def build(cell: Cell, seed: int, scale: int | None, log):
    """Graph, store, instances, and the service factory, from the seed."""
    import jax

    from repro.core.engine import EngineConfig
    from repro.core.scheduler import QueryScheduler, SchedulerConfig
    from repro.endpoint.service import EndpointService, ServiceConfig
    from repro.rdf.store import TripleStore

    gcfg = watdiv.GraphConfig.from_json(cell.config)
    if scale is not None:
        gcfg = watdiv.GraphConfig(**{**gcfg.__dict__, "scale": scale})
    t = time.perf_counter()
    g = watdiv.generate(gcfg, seed)
    store = TripleStore.build(g.s, g.p, g.o, n_terms=g.n_terms,
                              n_predicates=g.n_predicates)
    jax.block_until_ready(store.device)
    log(phase="store", triples=store.n_triples, terms=store.n_terms,
        build_s=time.perf_counter() - t)
    k = len(cell.mix["templates"])
    insts = tf.Instances(g, cell.mix["templates"],
                         int(cell.config["fit_rows"]), seed,
                         reserve=-(-int(cell.mix["clients"]) // k),
                         cap=int(cell.config["engine"]["cap"]))
    log(phase="instances", distinct=[int(r.shape[0]) for r in insts.roots],
        pool=insts.pool(), cut_share=insts.cut_share())
    ecfg = EngineConfig(**cell.config["engine"])
    scfg = SchedulerConfig(**cell.config["scheduler"])
    svcfg = ServiceConfig(**cell.config["service"])

    def service(planner=None):
        sched = QueryScheduler(store, ecfg, scfg, planner=planner)
        return EndpointService(sched, svcfg)
    return g, store, insts, service


def warm_up(cell: Cell, service, insts: tf.Instances, log):
    """Serve one drain shaped like the window's (``clients`` requests,
    the templates in turn, so each template's requests fill the same
    waves) of instances no window sends, so that the programs the window
    runs are compiled here; then, in a drain of their own, the instances
    whose bound passes the engine's ``cap`` (``tf.Instances.past_cap``),
    so that the larger capacities they need compile here too.  Returns
    the warm service, whose planner the window's service takes over."""
    from repro.endpoint.service import EndpointRequest

    svc = service()
    pools = insts.warmup()
    clients = int(cell.mix["clients"])
    drains = [[EndpointRequest(client=i, sparql=pools[i % len(pools)][
        (i // len(pools)) % len(pools[i % len(pools)])].text)
        for i in range(clients)]]
    big = insts.past_cap()
    if big:
        drains.append([EndpointRequest(client=i, sparql=inst.text)
                       for i, inst in enumerate(big)])
    t = time.perf_counter()
    for reqs in drains:
        bad = [x.status for x in svc.serve(reqs) if x.status != "ok"]
        if bad:
            raise BenchError(f"warm-up answers not ok: {bad[:3]}")
    log(phase="warmup", requests=clients, past_cap=len(big),
        wall_s=time.perf_counter() - t)
    return svc


# ------------------------------------------------------------------- metrics
def read_metric(name: str, w: Window):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(w)


def end_to_end(cell: Cell, w: Window, setup_s: float, horizon: float
               ) -> dict:
    lat = w.latencies()
    lat = np.where(np.isfinite(lat), lat, horizon)
    vals = {"qpm": (w.queries_per_s() or 0.0) * 60.0,
            "p50_s": quantile(lat, 0.50), "p95_s": quantile(lat, 0.95),
            "setup_s": setup_s}
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


# --------------------------------------------------------------------- check
def check(w: Window, answers) -> dict:
    """Every answer due in the window against the plain reference, row
    set for row set.  A request due in the window that was not answered
    ``ok`` (an error, a rejection, or no answer by the wait's end) counts
    as unanswered.  ``answers(instance)`` is the reference's row set.
    Returns the compared numbers with their limits."""
    due = w.due()
    wrong = unanswered = 0
    for r in due:
        if r.status != "ok":
            unanswered += 1
            continue
        want = answers(r.inst)
        got = canonical(r.rows)
        if got.shape != want.shape or got.tobytes() != want.tobytes():
            wrong += 1
    return {"wrong_answers": {"value": wrong, "limit": 0},
            "unanswered": {"value": unanswered, "limit": 0}}


# ----------------------------------------------------------------------- run
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, scale: int | None = None, wait_s: float = 60.0,
             log=None, fault=None) -> dict:
    """One run: set-up from ``t_start`` (the process's start), warm-up,
    the window, the reference check.  Returns the result object (the
    last line ``run.py`` prints).  ``scale`` shrinks the graph and
    ``fault`` (one of ``faults.FAULTS``) breaks the window's scheduler:
    both are for tests and the control, never for a measured run."""
    import jax

    from compileclock import CompileClock
    from repro import obs

    log = log or (lambda **kw: None)
    clock = CompileClock()
    g, store, insts, service = build(cell, seed, scale, log)
    warm = warm_up(cell, service, insts, log)
    svc = service(planner=warm.sched.planner)
    del warm
    if fault is not None:
        fault(svc)
    stream = insts.stream()
    gc.collect()
    gc.freeze()  # set-up's objects stay out of the window's collections
    if trace:
        obs.enable(trace=False)
        tdir = OUT / f"trace-{cell.name}"
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans only, no Python calls
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    marks = Marks(svc, clock, trace)
    setup_s = time.perf_counter() - t_start
    log(phase="window", setup_s=setup_s, **clock.read())
    t0, records = asyncio.run(_closed_loop(
        svc, marks, stream, int(cell.mix["clients"]), seconds, wait_s))
    gc.unfreeze()
    w = Window(cell.name, seconds, t0, records, snap=marks.snap,
               compiles=marks.compiles)
    if trace:
        jax.profiler.stop_trace()
        obs.disable()
        import trace_reduce
        w.trace = trace_reduce.reduce_dir(tdir)
        shutil.rmtree(tdir, ignore_errors=True)  # a few MB a run
    dev = jax.devices()[0]
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    del svc, store
    gc.collect()
    ref = Reference(g.s, g.p, g.o, g.n_terms)
    compared = check(w, lambda inst: ref.answers(inst.patterns,
                                                 inst.n_vars))
    correct = all(v["value"] <= v["limit"] for v in compared.values())
    due = w.due()
    failed = sum(1 for r in due if r.status != "ok")
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = read_metric(m["name"], w)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = end_to_end(cell, w, setup_s, seconds + wait_s)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(due),
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = w.trace["busy_s"]
        device["window_s"] = w.trace["window_s"]
        out["breakdown"] = w.trace["breakdown"]
    out["compared"] = compared
    log(phase="done", compiles_in_window=w.compiles,
        compiled_in_window=marks.programs,
        ok=len(w.ok()), due=len(due),
        timeline=[[round(r.sent - w.t0, 4), round(r.done - w.t0, 4),
                   r.status] for r in w.records])
    return out
