"""Async SPF endpoint: an admission-controlled request loop over the scheduler.

The paper's SPF server is an endpoint: clients POST SPARQL, the server
parses, star-decomposes and answers.  This module is that loop for the
repo's serving stack — an asyncio front door in front of
``QueryScheduler.submit``/``drain``:

- **requests** arrive as SPARQL text (parsed by ``endpoint.parse``) or
  pre-built ``BGP`` objects, tagged with a client id and an optional
  ``deadline_s`` budget;
- **admission control** bounds each client's in-flight requests
  (``max_inflight_per_client``) and the whole queue (``max_queue``):
  past either bound a request is answered immediately with
  ``status="rejected"`` and a ``retry_after_s`` hint instead of growing
  the queue — one flooding client cannot occupy the whole service, and
  sustained overload sheds load instead of queueing unboundedly;
- **fair wave packing**: when more requests wait than one scheduler
  drain should absorb (``wave_budget``), the batch is packed round-robin
  across clients in arrival order, so under overload every client makes
  progress proportional to its share of distinct turns, not its request
  volume;
- **interface accounting**: responses carry the query's ``QueryStats``
  and the service sums NRS/NTB at the interface into ``endpoint.*``
  instruments mounted on the scheduler's registry, so
  ``sched.snapshot()`` diffs cover the endpoint exactly like the
  scheduler/cache/planner tiers.

The scheduler drain itself runs in a worker thread
(``run_in_executor``), so the event loop keeps accepting (and
admission-rejecting) requests while a wave computes.

Failure model (the PR 9 failure plane; see the ROADMAP section of the
same name):

- every wave runs inside a **fault domain** (:meth:`_serve_domain`): an
  exception out of the drain bisects the wave — halves are re-submitted
  under a bounded retry budget with exponential backoff — until the
  poisoned query is isolated in a singleton, answered ``"error"``, and
  the rest of the wave is served untouched;
- ``_serve_wave`` guarantees **exactly-once resolution** in a
  ``finally``: any request the domain left unresolved is answered
  ``"error"``, and :meth:`_finish` is idempotent (an already-resolved
  future is never re-resolved, an admission slot never double-freed);
- the :meth:`run` loop **survives** arbitrary wave failures: a crashed
  wave resolves its own requests, the loop moves to the next arrivals;
- **deadlines** propagate into the scheduler and are checked at
  unit-step boundaries; an expired query resolves ``"timeout"`` with
  the stats accumulated so far, counted in ``sched.deadline_expired``.

Observability follows the repo's split: counts are per-service
``RegistryView`` instruments that tally regardless; latency histograms
(``endpoint.queue_wait_s``, ``endpoint.latency_s``), the
``endpoint.parse`` / ``endpoint.batch`` / ``endpoint.deliver`` /
retry/bisect spans (``obs.span``: profiler annotations plus
``endpoint.span_s.*`` seconds on the scheduler's registry) and the
``endpoint.request`` async events are recorded only when ``obs.enabled``
— and the tracer module stays unimported when tracing is off (the CI
import guard covers this module too).
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field

import numpy as np

from repro import faults, obs
from repro.core.engine import QueryStats, results_as_numpy
from repro.core.patterns import BGP
from repro.endpoint.parse import SPARQLParseError, parse_select


class EndpointStats(obs.RegistryView):
    """Interface-level tallies as ``endpoint.*`` registry instruments."""

    _PREFIX = "endpoint"
    _FIELDS = (
        "requests",  # everything that reached the front door
        "served",  # answered with rows
        "rejected",  # refused by per-client admission control
        "shed",  # refused by the global queue bound (overload shedding)
        "timeouts",  # expired at a unit-step boundary ("timeout" status)
        "errors",  # answered "error" (parse failures excluded)
        "parse_errors",
        "batches",  # scheduler drains issued (incl. retry/bisect drains)
        "drain_faults",  # drains that raised into the wave fault domain
        "drain_retries",  # fault-domain re-drains (incl. bisected halves)
        "drain_bisects",  # wave splits while isolating a poisoned query
        "nrs",  # requests sent past the interface (sum of QueryStats.nrs)
        "ntb",  # bytes transferred past the interface (sum of .ntb)
        "ingest_batches",  # write batches accepted through ingest()
        "ingest_triples",  # triples across those batches (inserts+deletes)
        "compactions",  # delta-into-base folds ingest() triggered
    )


@dataclass(frozen=True)
class EndpointRequest:
    """One client request: SPARQL text or a pre-built BGP.

    ``deadline_s`` is a per-request latency budget (seconds from
    enqueue); past it the query may resolve ``"timeout"`` at the next
    unit-step boundary instead of running to completion.
    """

    client: int
    sparql: str | None = None
    query: BGP | None = None
    deadline_s: float | None = None

    def __post_init__(self):
        if (self.sparql is None) == (self.query is None):
            raise ValueError("exactly one of sparql/query must be given")


@dataclass
class EndpointResponse:
    """The answer: rows + the same interface accounting ``QueryStats``
    carries, so endpoint NRS/NTB aggregate exactly like engine runs.

    ``status`` taxonomy:

    - ``"ok"``        — served; ``rows``/``n_results``/``stats`` set.
    - ``"rejected"``  — refused at admission (per-client bound or global
      overload shedding); nothing executed; ``retry_after_s`` hints when
      capacity should free up.
    - ``"timeout"``   — the request's ``deadline_s`` expired at a
      unit-step boundary; ``stats`` carries the work done so far,
      ``rows`` is ``None``.
    - ``"error"``     — parse failure, or the wave fault domain isolated
      this query as poisoned (every drain containing it failed);
      ``error`` carries the reason.

    Every submitted request resolves with exactly one of these.
    """

    client: int
    status: str  # "ok" | "rejected" | "timeout" | "error"
    rows: np.ndarray | None = None  # valid result rows [n_results, n_sel]
    n_results: int = 0
    nrs: int = 0  # requests the interface cost (1 for an endpoint query)
    ntb: int = 0  # bytes the interface transferred
    stats: QueryStats | None = None
    error: str | None = None
    latency_s: float = 0.0
    retry_after_s: float | None = None  # set on "rejected"


@dataclass(frozen=True)
class ServiceConfig:
    max_inflight_per_client: int = 64  # admission bound, per client
    wave_budget: int = 256  # max requests packed into one drain
    # global queue bound: past it arrivals are shed immediately with a
    # retry_after_s hint (status "rejected"), never queued
    max_queue: int = 1024
    # wave fault domain: how many re-drains (bisected halves included)
    # one wave's failure may spend before unresolved requests go
    # "error"; 8 levels isolate a poison out of a 256-request wave
    drain_retries: int = 8
    drain_backoff_s: float = 0.005  # base backoff, doubles per level
    term_ids: dict | None = None  # constant resolution for the parser


@dataclass
class _Pending:
    req: EndpointRequest
    future: asyncio.Future | None
    t_enq: float
    seq: int
    bgp: BGP | None = None
    select: tuple[int, ...] | None = None
    deadline: float | None = None  # absolute perf_counter instant


@dataclass
class EndpointService:
    """Asyncio request loop in front of one ``QueryScheduler``.

    Failure plane (see the module docstring for the full model): waves
    run inside a bisecting fault domain with retry/backoff, every
    request resolves exactly once (``"ok"``/``"rejected"``/
    ``"timeout"``/``"error"``), admission slots are freed exactly once,
    deadlines expire cooperatively in the scheduler, and the serving
    loop outlives arbitrary drain failures.
    """

    sched: object  # QueryScheduler
    cfg: ServiceConfig = field(default_factory=ServiceConfig)

    def __post_init__(self):
        self.stats = EndpointStats(self.sched.registry)
        self._waiting: list[_Pending] = []
        self._inflight: dict[int, int] = {}
        self._arrived: asyncio.Event | None = None
        self._seq = 0
        self._ewma_batch_s = 0.0  # smoothed drain wall, retry_after hints

    # ------------------------------------------------------------ requests
    async def submit(self, query: str | BGP, client: int = 0,
                     deadline_s: float | None = None) -> EndpointResponse:
        """Submit one request; resolves when its wave retires.

        Admission control answers immediately (no queueing) when the
        client is over its in-flight bound or the service over its
        global queue bound — with a ``retry_after_s`` hint either way.
        ``deadline_s`` bounds the request's latency budget.
        """
        req = EndpointRequest(client, sparql=query, deadline_s=deadline_s) \
            if isinstance(query, str) \
            else EndpointRequest(client, query=query, deadline_s=deadline_s)
        self.stats.requests += 1
        if len(self._waiting) >= self.cfg.max_queue:
            self.stats.shed += 1
            return EndpointResponse(client, "rejected",
                                    error="service overloaded",
                                    retry_after_s=self._retry_after())
        if self._inflight.get(client, 0) \
                >= self.cfg.max_inflight_per_client:
            self.stats.rejected += 1
            return EndpointResponse(client, "rejected",
                                    error="per-client in-flight bound",
                                    retry_after_s=self._retry_after())
        self._inflight[client] = self._inflight.get(client, 0) + 1
        t = time.perf_counter()
        pend = _Pending(req, asyncio.get_running_loop().create_future(),
                        t, self._seq,
                        deadline=None if deadline_s is None
                        else t + deadline_s)
        self._seq += 1
        self._waiting.append(pend)
        if obs.enabled and obs.tracer:
            obs.tracer.begin_async("endpoint.request", pend.seq,
                                   client=client)
        if self._arrived is not None:
            self._arrived.set()
        return await pend.future

    # -------------------------------------------------------------- ingest
    def ingest(self, insert=None, delete=None,
               compact_frac: float | None = 0.25) -> int:
        """The write entry point: apply a triple batch to the serving
        store's delta overlay, live.

        ``insert``/``delete`` are ``(s, p, o)`` array triples
        (``TripleStore.apply_delta``).  The batch routes through the
        scheduler's wave-boundary write queue: applied immediately when
        no drain is running, otherwise between waves — queries already
        in flight finish on the epoch view they started on, later waves
        serve the post-write epoch, and no rebuild ever blocks the
        request loop.

        ``compact_frac`` is the periodic-compaction threshold: once the
        delta outgrows that fraction of the base the overlay is folded
        into a fresh base index (``TripleStore.maybe_compact``) — off
        the serving path, never mid-drain, and with full cache/planner
        carry-over (a compaction changes no logical triple).  ``None``
        disables compaction here.  Returns the store epoch after the
        call.
        """
        self.stats.ingest_batches += 1
        self.stats.ingest_triples += sum(
            int(np.asarray(b[0]).size) for b in (insert, delete)
            if b is not None)
        ep = self.sched.ingest(insert=insert, delete=delete)
        if compact_frac is not None and not self.sched._draining:
            if self.sched.store.maybe_compact(frac=compact_frac):
                self.stats.compactions += 1
                self.sched._refresh_epoch()
                ep = self.sched.store.epoch
        return ep

    def _retry_after(self) -> float:
        """When should a rejected client come back?  Queue depth in
        waves x the smoothed drain wall (floored at 1 ms so a cold
        service still hints something actionable)."""
        waves = max(1.0, len(self._waiting) / max(1, self.cfg.wave_budget))
        return max(self._ewma_batch_s, 1e-3) * waves

    # ---------------------------------------------------------- wave packing
    def _pick_wave(self) -> list[_Pending]:
        """Round-robin across clients in arrival order, oldest first per
        client, up to ``wave_budget`` — volume does not buy extra turns."""
        budget = self.cfg.wave_budget
        if len(self._waiting) <= budget:
            wave, self._waiting = self._waiting, []
            return wave
        per_client: dict[int, list[_Pending]] = {}
        order: list[int] = []  # clients by first-waiting arrival
        for p in self._waiting:
            if p.req.client not in per_client:
                per_client[p.req.client] = []
                order.append(p.req.client)
            per_client[p.req.client].append(p)
        wave: list[_Pending] = []
        while len(wave) < budget:
            progressed = False
            for c in order:
                if per_client[c]:
                    wave.append(per_client[c].pop(0))
                    progressed = True
                    if len(wave) >= budget:
                        break
            if not progressed:
                break
        leftovers = [p for c in order for p in per_client[c]]
        leftovers.sort(key=lambda p: p.seq)  # preserve arrival order
        self._waiting = leftovers
        return wave

    # ------------------------------------------------------------- serving
    def _parse(self, pend: _Pending) -> bool:
        """Resolve the request to a BGP; answers the future on failure."""
        if pend.req.query is not None:
            pend.bgp = pend.req.query
            pend.select = tuple(range(pend.req.query.n_vars))
            return True
        try:
            if faults.plan is not None:
                faults.hit("parse", client=pend.req.client)
            parsed = parse_select(pend.req.sparql, self.cfg.term_ids)
        except (SPARQLParseError, faults.InjectedFault) as e:
            self.stats.parse_errors += 1
            self._finish(pend, EndpointResponse(
                pend.req.client, "error", error=str(e)))
            return False
        pend.bgp, pend.select = parsed.bgp, parsed.select
        return True

    def _finish(self, pend: _Pending, resp: EndpointResponse) -> None:
        """Resolve a request exactly once: an already-done future is left
        untouched (no double set_result, no double ``_inflight``
        decrement — the idempotence the chaos suite pins)."""
        if pend.future is not None and pend.future.done():
            return
        resp.latency_s = time.perf_counter() - pend.t_enq
        self._inflight[pend.req.client] -= 1
        if obs.enabled:
            self.sched.registry.observe("endpoint.latency_s", resp.latency_s)
            if obs.tracer:
                obs.tracer.end_async("endpoint.request", pend.seq,
                                     status=resp.status)
        if pend.future is not None:
            pend.future.set_result(resp)

    async def _drain_once(self, pends: list[_Pending]
                          ) -> tuple[dict, list[int]]:
        """Submit ``pends`` to the scheduler and drain in the worker
        thread.  The scheduler pops its queue at drain entry, so whether
        this raises before or after execution, re-calling with the same
        pends re-submits them fresh — the retry path needs no scheduler
        cooperation."""
        rids = [self.sched.submit(p.bgp, client=p.req.client,
                                  deadline=p.deadline) for p in pends]
        t0 = time.perf_counter()
        results = await asyncio.get_running_loop().run_in_executor(
            None, self.sched.drain)
        dt = time.perf_counter() - t0
        self._ewma_batch_s = dt if self._ewma_batch_s == 0.0 \
            else 0.8 * self._ewma_batch_s + 0.2 * dt
        self.stats.batches += 1
        return results, rids

    async def _serve_domain(self, pends: list[_Pending],
                            retries_left: int, backoff_s: float) -> None:
        """The wave fault domain: drain, and on an exception bisect.

        A failed multi-request drain splits in half; each half re-drains
        under a decremented retry budget and doubled backoff, so a
        poisoned query is isolated in O(log n) drains while its
        wave-mates are served by the clean halves.  A failed singleton
        retries under the remaining budget (transient faults recover),
        then resolves ``"error"``.  Requests this method resolves are
        resolved exactly once; ones it cannot serve are left for
        ``_serve_wave``'s finally backstop.
        """
        tr = obs.tracer if obs.enabled else None
        try:
            results, rids = await self._drain_once(pends)
        except Exception as e:
            self.stats.drain_faults += 1
            if tr:
                tr.instant("endpoint.drain_fault", requests=len(pends),
                           error=type(e).__name__)
            if retries_left <= 0:
                for p in pends:
                    self.stats.errors += 1
                    self._finish(p, EndpointResponse(
                        p.req.client, "error",
                        error=f"drain failed: {type(e).__name__}: {e}"))
                return
            self.stats.drain_retries += 1
            if backoff_s > 0:
                await asyncio.sleep(backoff_s)
            if len(pends) == 1:
                with obs.span("endpoint.retry", self.stats,
                              seq=pends[0].seq, retries_left=retries_left):
                    await self._serve_domain(pends, retries_left - 1,
                                             backoff_s * 2)
                return
            self.stats.drain_bisects += 1
            mid = len(pends) // 2
            with obs.span("endpoint.bisect", self.stats, left=mid,
                          right=len(pends) - mid, retries_left=retries_left):
                await self._serve_domain(pends[:mid], retries_left - 1,
                                         backoff_s * 2)
                await self._serve_domain(pends[mid:], retries_left - 1,
                                         backoff_s * 2)
            return
        with obs.span("endpoint.deliver", self.stats, requests=len(pends)):
            self._deliver(pends, rids, results)

    def _deliver(self, pends: list[_Pending], rids: list[int],
                 results: dict) -> None:
        for p, rid in zip(pends, rids):
            table, qstats = results[rid]
            if table is None:  # deadline expired at a unit boundary
                self.stats.timeouts += 1
                self._finish(p, EndpointResponse(
                    p.req.client, "timeout", stats=qstats,
                    error="deadline expired"))
                continue
            rows = results_as_numpy(table)
            if p.select is not None and tuple(p.select) \
                    != tuple(range(rows.shape[1])):
                rows = rows[:, list(p.select)]
            self.stats.served += 1
            self.stats.nrs += int(qstats.nrs)
            self.stats.ntb += int(qstats.ntb)
            self._finish(p, EndpointResponse(
                p.req.client, "ok", rows=rows,
                n_results=int(qstats.n_results), nrs=int(qstats.nrs),
                ntb=int(qstats.ntb), stats=qstats))

    async def _serve_wave(self, wave: list[_Pending]) -> None:
        t0 = time.perf_counter()
        with obs.span("endpoint.parse", self.stats, requests=len(wave)):
            live = [p for p in wave if self._parse(p)]
        if not live:
            return
        if obs.enabled:
            for p in live:
                self.sched.registry.observe("endpoint.queue_wait_s",
                                            t0 - p.t_enq)
        with obs.span("endpoint.batch", self.stats, requests=len(live)):
            try:
                await self._serve_domain(live, self.cfg.drain_retries,
                                         self.cfg.drain_backoff_s)
            finally:
                # exactly-once backstop: whatever the fault domain could
                # not resolve (including through an exception escaping
                # it) is answered "error" here, so no future is ever
                # stranded and no admission slot leaks (_finish is
                # idempotent)
                for p in live:
                    if p.future is None or not p.future.done():
                        self.stats.errors += 1
                        self._finish(p, EndpointResponse(
                            p.req.client, "error", error="wave aborted"))

    async def run(self, until_idle: bool = False) -> None:
        """The service loop: wait for arrivals, pack a fair wave, serve.

        ``until_idle=True`` returns once the queue is empty (the batch
        driver used by :meth:`serve` and the benchmarks); otherwise runs
        until cancelled.  The loop survives arbitrary wave failures: a
        crashed wave has already resolved its own requests (the
        ``_serve_wave`` finally), so the loop just moves on.
        """
        self._arrived = asyncio.Event()
        while True:
            if not self._waiting:
                if until_idle:
                    return
                self._arrived.clear()
                await self._arrived.wait()
            else:
                # yield once so concurrently-submitting clients enqueue
                # before the wave is packed
                await asyncio.sleep(0)
            if self._waiting:
                try:
                    await self._serve_wave(self._pick_wave())
                except Exception:
                    # the wave already resolved its requests in the
                    # finally backstop; the service must keep serving
                    if obs.enabled and obs.tracer:
                        obs.tracer.instant("endpoint.wave_crash")

    def serve(self, requests: list[EndpointRequest]
              ) -> list[EndpointResponse]:
        """Synchronous driver: issue ``requests`` concurrently (every
        client's stream in flight at once), run the loop until idle, and
        return responses in input order."""

        async def _go():
            subs = [asyncio.ensure_future(
                self.submit(r.sparql if r.sparql is not None else r.query,
                            r.client, deadline_s=r.deadline_s))
                    for r in requests]
            await asyncio.sleep(0)
            runner = asyncio.ensure_future(self.run(until_idle=True))
            out = await asyncio.gather(*subs)
            await runner
            return list(out)

        return asyncio.run(_go())
