"""QueryServer: admission queue + micro-batched fused evaluation.

Single queries arrive one at a time; the fused engines want batches of
a STATIC shape (every distinct (B, T) is an XLA compilation).  The
server bridges the two: requests admission-queue, and each pump drains
up to ``batch_size`` of them into one ``(batch_size, n_terms_budget)``
pad-and-mask evaluation — the exact shapes the per-segment kernels are
already warm for, so steady-state serving adds ZERO jit cache entries
(asserted the same way as the PR-3 churn test, and counted while
serving by the registry counter ``serve_compiles``).

Consistency: each micro-batch pins the index's current epoch view
(``LiveView``) and scores every request in the batch against it — a
response is bit-identical to the jnp oracle evaluated over the live
corpus AT THAT EPOCH, regardless of what ingest or background
maintenance does meanwhile.  The pin itself takes the write lock
NON-blockingly: if a writer holds it (mid-seal, mid-compact), the batch
serves from the previous pinned epoch instead of waiting — churn never
blocks the query path, it only delays epoch freshness by one
maintenance step.

Caching: results key on (padded query row, k, epoch).  An epoch advance
makes every older entry unreachable (see serve/cache.py), so hits are
always consistent with the epoch they will be reported against.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque

import jax
import numpy as np

from repro.core.live_index import LiveView, SegmentedIndex
from repro.obs.registry import GLOBAL, MetricsRegistry
from repro.obs.trace import StageAggregator, Trace, Tracer, annotate, stage
from repro.serve.cache import ResultCache
from repro.serve.metrics import ServerMetrics


# the two compile events a JAX program emits: a function traced to a
# jaxpr, and a lowered module compiled by the backend
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
# per thread: the ``serve_compiles`` counter of the server whose batch
# that thread is serving (unset outside a batch)
_in_batch = threading.local()
_listening = False
_listen_lock = threading.Lock()


def _on_compile(event: str, duration: float, **_kw) -> None:
    counter = getattr(_in_batch, "compiles", None)
    if counter is not None and event in COMPILE_EVENTS:
        counter.inc()


def _listen_for_compiles() -> None:
    """Register the process-wide compile listener once."""
    global _listening
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_compile)
            _listening = True


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """Static serving shapes + engine selection.

    ``batch_size`` and ``n_terms_budget`` ARE the compiled shapes: every
    micro-batch is padded to exactly (batch_size, n_terms_budget), and
    ``k`` fixes the candidate width — together with the live index's
    size classes that is the whole jit signature space of the serving
    path.  Queries wider than ``n_terms_budget`` are rejected at
    admission (never silently truncated).

    ``tune`` optionally pins a ``kernels.autotune.TuneConfig`` for every
    segment the server scores; ``None`` (the default) resolves each
    segment's geometry from the ACTIVE tuning table at trace time, per
    pinned epoch — segments sealed after ``autotune.set_active`` serve
    with their tuned kernels while warm size classes keep their compiled
    executables.

    ``layout_policy`` optionally pins a ``size_model.LayoutCostModel``
    alongside ``tune``: the server installs it on the index at
    construction, so maintenance-driven seals/compactions resolve their
    layout through the override ladder while every response still comes
    from an epoch-pinned view (layout changes only become visible at
    the next pin, like any other mutation).  ``None`` leaves the
    index's own policy untouched — bit-identical to pre-chooser
    serving.

    ``event_capacity`` optionally rebounds the index's maintenance
    event ring at server construction (``index.events.resize``) —
    long-lived serving meshes keep a deeper audit tail than the
    library default of 256 without touching ``SegmentedIndex`` call
    sites.  ``None`` leaves the index's ring as built.

    ``trace_sample`` samples end-to-end query traces: every Nth
    submitted ticket carries a ``repro.obs.Trace`` through queue wait,
    batch assembly, dispatch, the device wait, candidate merge, and
    response; a batch holding a sampled ticket also writes its leaf
    stages as ``serve.<stage>`` profiler annotations (``1`` traces
    every request, ``0`` — the default — disables tracing entirely: no
    span objects are constructed and no annotation is entered on the
    hot path, and results are bit-identical either way).
    """
    batch_size: int = 8
    n_terms_budget: int = 8
    k: int = 10
    cap: int | None = None
    rank_blend: float = 0.0
    engine: str = "pallas"
    mode: str = "candidates"
    backend: str = "pallas"
    cache_capacity: int = 4096
    tune: object | None = None
    layout_policy: object | None = None
    trace_sample: int = 0
    event_capacity: int | None = None


class Response:
    """One served result: top-k ids/scores + serving metadata.
    ``trace`` is the sampled ``repro.obs.Trace`` (None unless this
    ticket was sampled) — its top-level stage spans sum exactly to
    ``latency_us``.  ``status`` is ``"ok"`` for a served result; shed
    and shutdown resolutions carry ``"shed"`` / ``"shutdown"`` with
    empty ids (-1) and zero scores, so ``result()`` never blocks on a
    ticket the server has already given up on."""
    __slots__ = ("doc_ids", "scores", "epoch", "latency_us", "cached",
                 "trace", "status")

    def __init__(self, doc_ids, scores, epoch, latency_us, cached,
                 trace=None, status="ok"):
        self.doc_ids = doc_ids
        self.scores = scores
        self.epoch = epoch
        self.latency_us = latency_us
        self.cached = cached
        self.trace = trace
        self.status = status

    @property
    def ok(self) -> bool:
        return self.status == "ok"


class Ticket:
    """Admission handle: resolves to a Response when its batch lands.
    ``tenant`` scopes the result-cache partition the response may be
    served from (single-tenant servers leave it at ``"default"``)."""

    def __init__(self, row: np.ndarray, tenant: str = "default"):
        self.row = row
        self.tenant = tenant
        self.t_submit = time.perf_counter()
        self.response: Response | None = None
        self.trace: Trace | None = None
        self._done = threading.Event()

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: float | None = None) -> Response:
        if not self._done.wait(timeout):
            raise TimeoutError("query not served within timeout")
        return self.response


class QueryServer:
    """Micro-batched server over a SegmentedIndex.

    Drive it either synchronously (``submit`` + ``pump`` from one
    thread — deterministic, what the parity tests do) or with the
    worker thread (``start``/``stop``) while a ``serve.maintenance``
    thread churns the index in the background.  Writers (ingest,
    maintenance) must hold ``index_lock``; the server takes it only to
    pin a fresh view, and falls back to the previous pin when a writer
    has it.
    """

    def __init__(self, index: SegmentedIndex,
                 config: ServerConfig | None = None,
                 lock: threading.RLock | None = None):
        self.index = index
        self.config = config or ServerConfig()
        self.index_lock = lock if lock is not None else threading.RLock()
        self.cache = ResultCache(self.config.cache_capacity)
        self.registry = MetricsRegistry()
        self.metrics = ServerMetrics(registry=self.registry,
                                     cache=self.cache)
        self.tracer = Tracer(self.config.trace_sample)
        self.stages = StageAggregator(self.registry)
        # compiles while serving a batch: 0 in steady state, since
        # warmup() compiled every shape the batches use
        self._compiles = self.registry.counter("serve_compiles")
        # batches scored under BM25 (warm-up excluded)
        self._bm25_batches = self.registry.counter("serve_bm25_batches")
        _listen_for_compiles()
        self._register_index_gauges()
        self._queue: deque[Ticket] = deque()
        self._qlock = threading.Lock()
        self._work = threading.Event()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        with self.index_lock:
            if self.config.layout_policy is not None:
                index.layout_policy = self.config.layout_policy
            if self.config.event_capacity is not None:
                index.events.resize(self.config.event_capacity)
            self._pinned: LiveView = index.view()
        self._observe_pin(self._pinned)
        self._purged_epoch = self._pinned.epoch
        self.metrics.observe_layout_mix(self._pinned.layout_mix())

    # -- observability ------------------------------------------------------

    def _register_index_gauges(self) -> None:
        """Expose live-index state + maintenance counters as callback
        gauges, read at snapshot time (no polling thread)."""
        ix = self.index
        for name, fn in (
                ("index_epoch", lambda: ix.epoch),
                ("index_segments", lambda: ix.num_segments),
                ("index_docs", lambda: ix.num_docs),
                ("index_live_docs", lambda: ix.live_doc_count),
                ("index_delta_fill", lambda: ix.delta_fill),
                ("index_seals", lambda: ix.stats.seals),
                ("index_compactions", lambda: ix.stats.compactions),
                ("index_layout_rewrites", lambda: ix.stats.layout_rewrites),
                ("index_postings_merged", lambda: ix.stats.postings_merged),
                ("index_deletes", lambda: ix.stats.deletes),
                ("index_events_total", lambda: ix.events.total)):
            if self.registry.get(name) is None:
                self.registry.register_callback(name, fn)

    def _observe_pin(self, view: LiveView) -> None:
        """Gauges of the pinned epoch's length statistics: its live
        tokens and their average over live documents (BM25's avgdl),
        set at each pin."""
        self.registry.gauge("index_live_tokens").set(view.live_tokens)
        self.registry.gauge("index_avgdl").set(view.avgdl)

    def metrics_snapshot(self, include_global: bool = True) -> dict:
        """The stable export (see ``repro.obs.registry``): this
        server's registry — counters, cache gauges, index gauges,
        per-stage histograms — merged with the process-global engine
        counters (pair overflow, truncated terms)."""
        snap = self.registry.snapshot()
        if include_global:
            for name, m in GLOBAL.snapshot().items():
                snap.setdefault(name, m)
        return snap

    def stage_summary(self) -> dict:
        """Per-stage latency breakdown ({stage: {count, sum, p50,
        p99}}) aggregated from sampled traces."""
        return self.stages.summary()

    def events(self, n: int | None = None, kind: str | None = None) -> list:
        """The last ``n`` maintenance events from the index's bounded
        event log (seal/compact/rewrite/ingest/delete/...)."""
        return self.index.events.tail(n, kind=kind)

    # -- admission ----------------------------------------------------------

    def _make_ticket(self, query_hashes, tenant: str = "default") -> Ticket:
        """Validate + zero-pad one query into a Ticket (not yet
        enqueued) — the shared admission front half, so subclasses can
        decide a ticket's fate (enqueue vs shed) after it exists."""
        qh = np.atleast_1d(np.asarray(query_hashes, np.uint32))
        if qh.ndim != 1:
            raise ValueError(
                f"submit takes ONE query (a 1-D hash vector), got shape "
                f"{qh.shape} — submit batch rows individually; the server "
                "does the batching")
        t = self.config.n_terms_budget
        if qh.shape[0] > t:
            raise ValueError(
                f"query has {qh.shape[0]} term slots > n_terms_budget={t} "
                "(widen the budget; truncation would drop terms silently)")
        row = np.zeros(t, np.uint32)
        row[:qh.shape[0]] = qh
        ticket = Ticket(row, tenant=tenant)
        if self.tracer.enabled:
            ticket.trace = self.tracer.sample()
        return ticket

    def submit(self, query_hashes) -> Ticket:
        """Enqueue one query (u32 term-hash vector, <= n_terms_budget
        wide; it is zero-padded to the budget).  Returns a Ticket."""
        ticket = self._make_ticket(query_hashes)
        with self._qlock:
            self._queue.append(ticket)
        self._work.set()
        return ticket

    def query(self, query_hashes, timeout: float = 60.0) -> Response:
        """Synchronous convenience: submit, then either wait on the
        worker thread or pump inline until served."""
        ticket = self.submit(query_hashes)
        if self._thread is None:
            while not ticket.done():
                if self.pump() == 0 and not ticket.done():
                    raise RuntimeError("queue drained without serving "
                                       "the submitted ticket")
        return ticket.result(timeout)

    @property
    def pending(self) -> int:
        with self._qlock:
            return len(self._queue)

    # -- view pinning ---------------------------------------------------

    def refresh_view(self) -> LiveView:
        """Pin the freshest view available WITHOUT waiting on writers:
        non-blocking lock probe, fall back to the previous pinned epoch
        when a writer is mid-mutation."""
        if self.index_lock.acquire(blocking=False):
            try:
                self._pinned = self.index.view()
            finally:
                self.index_lock.release()
            self._observe_pin(self._pinned)
        return self._pinned

    @property
    def pinned_epoch(self) -> int:
        return self._pinned.epoch

    # -- the micro-batch loop -------------------------------------------

    def pump(self, max_batches: int = 1) -> int:
        """Serve up to ``max_batches`` micro-batches from the queue;
        returns the number of requests answered."""
        served = 0
        for _ in range(max_batches):
            batch = self._take_batch()
            if not batch:
                break
            _in_batch.compiles = self._compiles
            try:
                self._serve_batch(batch)
            finally:
                _in_batch.compiles = None
            served += len(batch)
        return served

    def _take_batch(self) -> list[Ticket]:
        with self._qlock:
            n = min(len(self._queue), self.config.batch_size)
            batch = [self._queue.popleft() for _ in range(n)]
            if not self._queue:
                self._work.clear()
        return batch

    def _serve_batch(self, batch: list[Ticket]) -> None:
        cfg = self.config
        # stage boundaries are SHARED timestamps: queue_wait ends where
        # assemble (or the cache-hit span) starts, so a sampled ticket's
        # top-level spans sum EXACTLY to its measured e2e latency
        traced = [t for t in batch if t.trace is not None]
        t_batch = time.perf_counter() if traced else 0.0
        for t in traced:
            t.trace.span("queue_wait", t0=t.t_submit).end(t_batch)
        # batch-level spans (assembly, scoring + per-segment/merge
        # children) are recorded ONCE and adopted by every sampled
        # ticket the batch scores — the work is genuinely shared
        btr = Trace() if traced else None
        pending: list[tuple[Ticket, tuple]] = []
        with stage(btr, "assemble", t0=t_batch) as asm:
            view = self.refresh_view()
            epoch = view.epoch
            self.metrics.observe_epoch(epoch)
            if epoch != self._purged_epoch:
                # stale-epoch entries are already unreachable (keys
                # carry their epoch); reclaim them once per advance
                self.cache.purge_below(epoch)
                self._purged_epoch = epoch
                # once per epoch advance: report the layout mix this
                # epoch's stack converged to (seal/compact/rewrite all
                # repin)
                self.metrics.observe_layout_mix(view.layout_mix())
            for ticket in batch:
                key = self.cache.make_key(ticket.row, cfg.k, epoch)
                hit = self.cache.get(key)
                if hit is not None:
                    self._respond(ticket, hit[0], hit[1], epoch,
                                  cached=True, stage_t0=t_batch)
                else:
                    pending.append((ticket, key))
            qb = np.zeros((cfg.batch_size, cfg.n_terms_budget), np.uint32)
            for i, (ticket, _) in enumerate(pending):
                qb[i] = ticket.row
            if asm is not None:
                asm.attrs.update(epoch=epoch, fill=len(pending),
                                 padded_slots=cfg.batch_size - len(pending))
        if not pending:
            return
        score = (btr.span("score", t0=asm.t1, engine=cfg.engine,
                          mode=cfg.mode, backend=cfg.backend,
                          segments=view.num_segments)
                 if btr is not None else None)
        result = view.topk(qb, cfg.k, cap=cfg.cap,
                           rank_blend=cfg.rank_blend, engine=cfg.engine,
                           mode=cfg.mode, backend=cfg.backend,
                           tune=cfg.tune, trace=btr)
        if view.ranking.saturates:
            self._bm25_batches.inc()
        with stage(btr, "fetch", parent="score"):
            ids = np.asarray(result.doc_ids)
            scores = np.asarray(result.scores)
        if score is not None:
            score.end()
        t_scored = score.t1 if score is not None else None
        with annotate("respond", enabled=btr is not None):
            for i, (ticket, key) in enumerate(pending):
                self.cache.put(key, ids[i], scores[i])
                if ticket.trace is not None:
                    ticket.trace.adopt(btr.spans)
                self._respond(ticket, ids[i].copy(), scores[i].copy(),
                              epoch, cached=False, stage_t0=t_scored)
        self.metrics.batches += 1
        self.metrics.batched_queries += len(pending)
        self.metrics.padded_slots += cfg.batch_size - len(pending)

    def _respond(self, ticket: Ticket, doc_ids, scores, epoch: int,
                 cached: bool, stage_t0: float | None = None) -> None:
        now = time.perf_counter()
        latency_us = (now - ticket.t_submit) * 1e6
        tr = ticket.trace
        if tr is not None:
            # final stage closes at the SAME clock reading latency_us is
            # computed from — the stage sum is the e2e latency, exactly
            if stage_t0 is not None:
                tr.span("cache_hit" if cached else "respond",
                        t0=stage_t0, epoch=epoch).end(now)
            self.stages.observe_trace(tr)
            self.stages.observe("e2e", latency_us)
        ticket.response = Response(doc_ids, scores, epoch, latency_us,
                                   cached, trace=tr)
        self.metrics.record_response(latency_us)
        ticket._done.set()

    # -- warmup ---------------------------------------------------------

    def warmup(self) -> None:
        """Compile the serving path's static shapes: one full-width
        batch of empty queries through the current view (shapes do not
        depend on query content).  Call again after the index mints a
        NEW size class if strict zero-compile serving matters; warm
        classes stay warm."""
        view = self.refresh_view()
        cfg = self.config
        qb = np.zeros((cfg.batch_size, cfg.n_terms_budget), np.uint32)
        view.topk(qb, cfg.k, cap=cfg.cap, rank_blend=cfg.rank_blend,
                  engine=cfg.engine, mode=cfg.mode, backend=cfg.backend,
                  tune=cfg.tune)

    # -- worker thread ---------------------------------------------------

    def start(self) -> None:
        """Spawn the worker thread (idempotent)."""
        if self._thread is not None:
            return
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                if self.pump(max_batches=4) == 0:
                    self._work.wait(timeout=0.005)
            self.pump(max_batches=1_000_000)   # drain on shutdown

        self._thread = threading.Thread(target=loop, name="query-server",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        """Stop the worker (if running) and resolve every still-queued
        ticket with a ``status="shutdown"`` Response — ``result()``
        must never block until timeout on a server that has stopped.
        The worker drains the queue normally first, so only tickets
        that raced the shutdown (or pump-mode leftovers) are failed."""
        if self._thread is not None:
            self._stop.set()
            self._work.set()
            self._thread.join(timeout=30.0)
            self._thread = None
        self._fail_pending()

    def _fail_pending(self) -> int:
        with self._qlock:
            leftover = list(self._queue)
            self._queue.clear()
            self._work.clear()
        for ticket in leftover:
            self._resolve_shutdown(ticket)
        return len(leftover)

    def _resolve_shutdown(self, ticket: Ticket) -> None:
        """Resolve one unserved ticket as shed-by-shutdown (overridden
        by the mesh to count/log it as a shed)."""
        now = time.perf_counter()
        k = self.config.k
        tr = ticket.trace
        if tr is not None:
            tr.span("shed", t0=ticket.t_submit, reason="shutdown").end(now)
            self.stages.observe_trace(tr)
        ticket.response = Response(
            np.full(k, -1, np.int32), np.zeros(k, np.float32),
            self._pinned.epoch, (now - ticket.t_submit) * 1e6,
            False, trace=tr, status="shutdown")
        self.registry.counter("serve_shutdown_unserved").inc()
        ticket._done.set()
