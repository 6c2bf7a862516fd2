"""Observability tier: span tracing, the unified metrics registry, the
maintenance event log, and their serving/kernel integration.

The two load-bearing contracts:

  * stage spans share boundary timestamps, so a sampled response's
    top-level durations sum (exactly; asserted at 5%) to its measured
    e2e latency, and tracing NEVER changes engine output — traced and
    untraced servers answer bit-identically over a randomized churn
    schedule;
  * with tracing disabled (the default) no Span/Trace object is
    constructed anywhere on the serving path — asserted by making
    construction raise.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build, compaction, layouts, query
from repro.core.live_index import SegmentedIndex
from repro.kernels import ops
from repro.obs import trace as obs_trace
from repro.obs.registry import (GLOBAL, EventLog, MetricsRegistry,
                                parse_prometheus, snapshot_from_json,
                                snapshot_to_json)
from repro.obs.trace import StageAggregator, Trace, Tracer
from repro.serve import QueryServer, ServerConfig
from repro.serve.cache import ResultCache
from repro.serve.metrics import LatencyWindow, ServerMetrics, percentiles
from repro.text import corpus


def _slice(tc, a, b):
    return dataclasses.replace(tc, doc_term_ids=tc.doc_term_ids[a:b],
                               doc_counts=tc.doc_counts[a:b],
                               num_docs=b - a)


# ---------------------------------------------------------------------------
# percentiles / LatencyWindow vs the numpy reference
# ---------------------------------------------------------------------------


def test_percentiles_match_numpy_reference():
    rng = np.random.default_rng(3)
    samples = rng.lognormal(4.0, 1.5, size=257)
    p = percentiles(samples, (50, 90, 99))
    for q in (50, 90, 99):
        assert p[f"p{q}"] == pytest.approx(
            float(np.percentile(samples, q)), rel=1e-12)


def test_percentiles_empty_and_single_sample():
    assert percentiles([]) == {"p50": 0.0, "p99": 0.0}
    p = percentiles([42.5])
    assert p["p50"] == 42.5 and p["p99"] == 42.5


def test_latency_window_edges():
    w = LatencyWindow()
    # empty window: zeros everywhere, qps 0 (not NaN/raise)
    s = w.summary()
    assert s == {"count": 0, "p50_us": 0.0, "p99_us": 0.0,
                 "mean_us": 0.0, "qps": 0.0}
    # single sample: percentiles collapse to it, qps still 0 (one
    # completion spans no interval)
    w.record(100.0)
    s = w.summary()
    assert s["count"] == 1 and s["p50_us"] == 100.0
    assert s["qps"] == 0.0
    # zero wall span with >= 2 completions must not divide by zero
    w.record(50.0)
    w._last = w._first
    assert w.qps() == 0.0
    w.reset()
    assert w.count == 0 and w.qps() == 0.0


# ---------------------------------------------------------------------------
# spans and the tracer
# ---------------------------------------------------------------------------


def test_span_shared_boundaries_sum_exactly():
    tr = Trace()
    a = tr.span("queue_wait", t0=1.0).end(2.5)
    b = tr.span("score", t0=a.t1).end(4.0)
    tr.span("segment", t0=3.0, parent="score").end(3.5)  # child: excluded
    tr.span("respond", t0=b.t1).end(5.0)
    d = tr.stage_durations()
    assert set(d) == {"queue_wait", "score", "respond"}
    assert sum(d.values()) == pytest.approx((5.0 - 1.0) * 1e6)
    assert tr.total_us() == pytest.approx(4e6)


def test_tracer_sampling_and_disabled():
    t = Tracer(sample_every=3)
    got = [t.sample() is not None for _ in range(9)]
    assert got == [False, False, True] * 3
    off = Tracer(sample_every=0)
    assert not off.enabled
    assert all(off.sample() is None for _ in range(5))


def test_stage_aggregator_feeds_registry_histograms():
    reg = MetricsRegistry()
    agg = StageAggregator(reg)
    tr = Trace()
    tr.span("score", t0=0.0).end(0.001)          # 1000us
    tr.span("respond", t0=0.001).end(0.0015)     # 500us
    agg.observe_trace(tr)
    agg.observe("score", 3000.0)
    s = agg.summary()
    assert s["score"]["count"] == 2
    assert s["score"]["sum"] == pytest.approx(4000.0)
    assert reg.get("serve_stage_score_us") is not None
    assert "type" not in s["score"]              # summary strips it
    agg.reset()
    assert agg.summary()["score"]["count"] == 0


# ---------------------------------------------------------------------------
# registry: instruments, export round-trips, failure modes
# ---------------------------------------------------------------------------


def _populated_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    reg.counter("serve_requests").inc(123)
    reg.gauge("delta_fill").set(0.62519731)
    reg.register_callback("index_epoch", lambda: 7)
    h = reg.histogram("serve_stage_score_us")
    for v in (101.5, 220.25, 3000.125, 47.0625):
        h.observe(v)
    return reg


def test_registry_snapshot_json_roundtrip():
    reg = _populated_registry()
    snap = reg.snapshot()
    assert snap["serve_requests"] == {"type": "counter", "value": 123}
    assert snap["index_epoch"] == {"type": "gauge", "value": 7.0}
    assert snap["serve_stage_score_us"]["count"] == 4
    restored = snapshot_from_json(snapshot_to_json(snap))
    assert restored == snap
    # and the JSON is plain-json safe (no numpy scalars leaked)
    json.dumps(snap)


def test_registry_prometheus_roundtrip():
    reg = _populated_registry()
    text = reg.to_prometheus()
    assert "# TYPE serve_requests counter" in text
    assert '{quantile="0.5"}' in text
    assert parse_prometheus(text) == reg.snapshot()


def test_registry_get_or_create_and_type_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("x_total")
    assert reg.counter("x_total") is c
    with pytest.raises(TypeError):
        reg.gauge("x_total")
    reg.register_callback("live", lambda: 1.0)
    with pytest.raises(ValueError):
        reg.register_callback("live", lambda: 2.0)
    with pytest.raises(ValueError):
        reg.counter("bad name")
    with pytest.raises(ValueError):
        reg.counter("serve_requests").inc(-1)


def test_registry_reset_spares_callback_gauges():
    reg = _populated_registry()
    reg.reset()
    snap = reg.snapshot()
    assert snap["serve_requests"]["value"] == 0
    assert snap["serve_stage_score_us"]["count"] == 0
    assert snap["index_epoch"]["value"] == 7.0   # reads live state


# ---------------------------------------------------------------------------
# event log
# ---------------------------------------------------------------------------


def test_event_log_bounded_ring_and_counts():
    log = EventLog(capacity=4)
    for i in range(10):
        log.emit("seal", epoch=i)
    log.emit("compact", merged=3)
    assert len(log) == 4                 # ring evicted the oldest
    assert log.total == 11               # ...but the count survived
    assert log.counts() == {"seal": 10, "compact": 1}
    tail = log.tail(2)
    assert [e["kind"] for e in tail] == ["seal", "compact"]
    assert tail[-1]["seq"] == 11 and tail[-1]["merged"] == 3
    assert [e["epoch"] for e in log.tail(kind="seal")] == [7, 8, 9]


def test_segmented_index_emits_lifecycle_events():
    tc = corpus.generate(corpus.CorpusSpec(num_docs=600, vocab=200,
                                           avg_distinct=16, seed=6))
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=128,
                        delta_posting_capacity=128 * 64,
                        policy=compaction.TieredPolicy(size_ratio=2.0,
                                                       min_run=2))
    for a in range(0, 600, 150):
        si.add_batch(_slice(tc, a, a + 150))
        si.seal()
    si.compact(all_segments=True)
    si.delete([1, 3])
    counts = si.events.counts()
    assert counts["ingest"] == 4 and counts["seal"] >= 4
    assert counts["compact"] >= 1 and counts["delete"] == 1
    seal = si.events.tail(kind="seal")[0]
    for field in ("epoch", "doc_base", "docs", "postings", "size_class",
                  "layout", "chooser_reason", "duration_us"):
        assert field in seal, field
    compact = si.events.tail(kind="compact")[-1]
    assert compact["postings_in"] >= compact["merged"] >= 2


# ---------------------------------------------------------------------------
# ServerMetrics: registry-backed counters, complete summary, deprecation
# ---------------------------------------------------------------------------


def test_server_metrics_registry_backed_and_summary_complete():
    cache = ResultCache(capacity=8)
    m = ServerMetrics(cache=cache)
    m.requests += 3
    m.batched_queries, m.padded_slots = 6, 2
    assert m.registry.counter("serve_requests").value == 3
    assert m.batch_fill() == pytest.approx(0.75)
    key = cache.make_key(np.asarray([1, 2], np.uint32), 10, 0)
    cache.put(key, np.asarray([5]), np.asarray([1.0]))
    cache.get(key)
    cache.get(cache.make_key(np.asarray([9, 9], np.uint32), 10, 0))
    s = m.summary()                      # no cache argument needed
    assert s["cache_hits"] == 1 and s["cache_misses"] == 1
    assert s["cache_hit_rate"] == pytest.approx(0.5)
    snap = m.snapshot()
    assert snap["cache_hits"]["value"] == 1.0
    assert snap["serve_requests"]["value"] == 3
    m.reset()
    assert m.requests == 0
    assert m.snapshot()["cache_hits"]["value"] == 1.0   # cache untouched


def test_server_metrics_summary_cache_arg_deprecated():
    cache = ResultCache(capacity=4)
    m = ServerMetrics(cache=cache)
    other = ResultCache(capacity=4)
    other.get(other.make_key(np.asarray([1], np.uint32), 10, 0))  # a miss
    with pytest.warns(DeprecationWarning):
        s = m.summary(other)
    # the parameter is inert: the attached cache is reported, the
    # passed one's counters never leak into the summary
    assert s["cache_hits"] == cache.hits
    assert s["cache_misses"] == cache.misses == 0

    # a metrics object with NO attached cache: the deprecated argument
    # still warns and still reports nothing (migration is attach_cache)
    bare = ServerMetrics()
    with pytest.warns(DeprecationWarning):
        s2 = bare.summary(other)
    assert "cache_hits" not in s2


# ---------------------------------------------------------------------------
# engine counters on the GLOBAL registry (jit-safe via debug.callback)
# ---------------------------------------------------------------------------


def test_overflow_counter_increments_on_engineered_corpus():
    """The engineered overflow corpus (2600 docs / 80 terms / seed 1)
    under a deliberately narrow budget drops real pairs; the loud-
    overflow warning must now ALSO land in the global registry counter
    so capacity pressure is visible without scraping stderr."""
    from repro.kernels.fused_decode_score import build_batched_pairs

    tc = corpus.generate(corpus.CorpusSpec(num_docs=2600, vocab=80,
                                           avg_distinct=20, seed=1))
    host = build.bulk_build(tc)
    ix = layouts.build_blocked(host)
    cap = host.max_posting_len
    th = host.term_hashes
    qh = jnp.asarray(th[th != 0][None, :])
    t_ids = jnp.where(qh != 0, ix.lookup_terms(qh), -1)
    m = min(max(-(-cap // ix.block), 1), max(ix.max_blocks_per_term, 1))
    cb, cv, cq, cw, cc = ops.expand_block_candidates(
        ix.block_offsets, t_ids, jnp.ones_like(t_ids, jnp.float32), m,
        ix.block, cap)
    tf, tcn, n_tiles = ops.routing_spans(ix, 512)
    # one pair short of the exact whole-index budget
    narrow = ops.scaled_pairs_budget(ix, 512) - 1
    *_, ovf = build_batched_pairs(
        cb, cv, cq, cw.astype(jnp.float32), tf, tcn, n_tiles, 1, narrow,
        cand_cap=cc)
    assert int(ovf) > 0
    c = GLOBAL.counter("engine_pair_overflow")
    before = c.value
    ops.warn_on_overflow(ovf, "test_obs narrow budget")
    jax.effects_barrier()
    assert c.value == before + int(ovf)
    # zero overflow takes the silent branch: no increment
    ops.warn_on_overflow(jnp.zeros((), jnp.int32), "test_obs zero")
    jax.effects_barrier()
    assert c.value == before + int(ovf)


def test_overflow_counter_increments_under_jit():
    c = GLOBAL.counter("engine_pair_overflow")
    before = c.value

    @jax.jit
    def f(o):
        ops.warn_on_overflow(o, "test_obs jitted")
        return o + 1

    f(jnp.asarray(7, jnp.int32)).block_until_ready()
    jax.effects_barrier()
    assert c.value == before + 7


def test_truncated_terms_counter_via_conjunctive(small_host):
    ix = layouts.build_csr(small_host)
    df = np.asarray(small_host.df)
    # query the two most frequent terms with a cap below both dfs:
    # the gather truncates both posting lists
    busy = np.argsort(df)[-2:]
    cap = int(df[busy].min()) - 1
    assert cap >= 1
    qh = jnp.asarray(small_host.term_hashes[busy])
    c = GLOBAL.counter("engine_truncated_terms")
    before = c.value
    _, stats = query.conjunctive_filter(ix, qh, k=5, cap=cap)
    jax.effects_barrier()
    expect = int(stats["truncated_terms"])
    assert expect == 2
    assert c.value == before + expect
    # host-side ints route through the same counter without jax
    ops.record_truncated(3)
    assert c.value == before + expect + 3
    ops.record_truncated(0)
    assert c.value == before + expect + 3


# ---------------------------------------------------------------------------
# serving integration: disabled-tracing overhead, stage sums, parity
# ---------------------------------------------------------------------------


def _mini_corpus():
    return corpus.generate(corpus.CorpusSpec(num_docs=900, vocab=300,
                                             avg_distinct=16, seed=9))


def _make_server(tc, trace_sample, cache_capacity=4096):
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=128,
                        delta_posting_capacity=128 * 64,
                        policy=compaction.TieredPolicy(size_ratio=4.0,
                                                       min_run=4))
    si.add_batch(_slice(tc, 0, 300))
    si.seal()
    cfg = ServerConfig(batch_size=4, n_terms_budget=8, k=10,
                       cache_capacity=cache_capacity,
                       trace_sample=trace_sample)
    return si, QueryServer(si, cfg)


def _drive(si, server, tc, pool, *, seed=17, steps=8):
    """One randomized churn schedule: ingest/seal/compact interleaved
    with micro-batches.  Deterministic given ``seed``, so two
    identically-seeded servers see identical schedules."""
    rng = np.random.default_rng(seed)
    responses = []
    a = 300
    for step in range(steps):
        op = rng.integers(3)
        if op == 0 and a + 100 <= tc.num_docs:
            with server.index_lock:
                si.add_batch(_slice(tc, a, a + 100))
            a += 100
        elif op == 1:
            with server.index_lock:
                si.seal()
        elif op == 2:
            with server.index_lock:
                si.compact()
        tickets = [server.submit(pool[rng.integers(len(pool))])
                   for _ in range(4)]
        while server.pending:
            server.pump()
        responses += [t.result(timeout=120.0) for t in tickets]
    return responses


def test_disabled_tracing_constructs_no_span_objects(monkeypatch):
    """trace_sample=0 (the default) must never construct Span/Trace on
    the serving path — near-zero cost when off is the contract."""
    tc = _mini_corpus()
    si, server = _make_server(tc, trace_sample=0)
    server.warmup()

    def boom(self, *a, **k):
        raise AssertionError(f"{type(self).__name__} constructed with "
                             "tracing disabled")

    monkeypatch.setattr(obs_trace.Span, "__init__", boom)
    monkeypatch.setattr(obs_trace.Trace, "__init__", boom)
    pool = corpus.sample_query_terms(
        build.bulk_build(_slice(tc, 0, 300)).df, tc.term_hashes, 8, 3,
        num_docs=300, seed=2)
    responses = _drive(si, server, tc, pool, steps=4)
    assert len(responses) == 16
    assert all(r.trace is None for r in responses)
    assert server.stage_summary() == {}


def test_traced_stage_sums_and_bitwise_parity_under_churn():
    """The acceptance criterion: per-response stage durations sum to
    within 5% of the measured e2e latency (the shared-boundary
    construction makes it exact), and a traced server's outputs are
    BIT-identical to an untraced server's over the same randomized
    churn schedule — observability must never perturb results."""
    tc = _mini_corpus()
    pool = corpus.sample_query_terms(
        build.bulk_build(_slice(tc, 0, 300)).df, tc.term_hashes, 8, 3,
        num_docs=300, seed=2)
    si_t, srv_t = _make_server(tc, trace_sample=1)
    si_u, srv_u = _make_server(tc, trace_sample=0)
    srv_t.warmup()
    srv_u.warmup()
    traced = _drive(si_t, srv_t, tc, pool, seed=21)
    plain = _drive(si_u, srv_u, tc, pool, seed=21)

    assert len(traced) == len(plain)
    for rt, ru in zip(traced, plain):
        assert rt.trace is not None and ru.trace is None
        assert rt.epoch == ru.epoch
        np.testing.assert_array_equal(np.asarray(rt.doc_ids),
                                      np.asarray(ru.doc_ids))
        np.testing.assert_array_equal(
            np.asarray(rt.scores, np.float32).view(np.uint32),
            np.asarray(ru.scores, np.float32).view(np.uint32))
        stages = rt.trace.stage_durations()
        total = sum(stages.values())
        assert total == pytest.approx(rt.latency_us, rel=0.05)
        if rt.cached:
            assert set(stages) == {"queue_wait", "cache_hit"}
        else:
            assert set(stages) == {"queue_wait", "assemble", "score",
                                   "respond"}
            kids = {s.name for s in rt.trace.spans if s.parent == "score"}
            assert "segment" in kids and "merge" in kids
            seg = next(s for s in rt.trace.spans if s.name == "segment")
            for attr in ("size_class", "layout", "tile",
                         "candidate_bytes", "posting_bytes"):
                assert attr in seg.attrs, attr

    # uncached responses exist and their scoring really took the traced
    # path (epochs advanced under churn)
    assert any(not r.cached for r in traced)
    summary = srv_t.stage_summary()
    assert summary["e2e"]["count"] == len(traced)
    assert summary["score"]["p99"] > 0
    # the server-side snapshot merges per-server and GLOBAL engine
    # counters into one export (get-or-create so the assertion holds
    # even when this test runs before any engine counter fires)
    GLOBAL.counter("engine_pair_overflow")
    snap = srv_t.metrics_snapshot()
    assert "engine_pair_overflow" in snap
    assert snap["serve_requests"]["value"] == len(traced)
    assert "serve_stage_score_us" in snap
    json.dumps(snap)
    # maintenance events are queryable from the server
    assert any(e["kind"] == "seal" for e in srv_t.events())


# ---------------------------------------------------------------------------
# CI artifact gate: malformed registry sections fail loudly
# ---------------------------------------------------------------------------


def test_check_regression_rejects_malformed_registry():
    from benchmarks.check_regression import check_registry_section

    ok = {"registry": {"serve_requests": {"type": "counter", "value": 3},
                       "delta_fill": {"type": "gauge", "value": 0.5},
                       "serve_stage_score_us": {
                           "type": "histogram", "count": 2, "sum": 10.0,
                           "p50": 5.0, "p99": 9.0}},
          "stages": {"score": {"count": 2, "p50": 5.0, "p99": 9.0,
                               "sum": 10.0}}}
    assert check_registry_section(ok) == []
    assert check_registry_section({}) != []              # missing
    assert check_registry_section({"registry": {}}) != []  # empty
    bad_counter = json.loads(json.dumps(ok))
    bad_counter["registry"]["serve_requests"]["value"] = "3"
    assert any("counter" in p for p in check_registry_section(bad_counter))
    bad_hist = json.loads(json.dumps(ok))
    del bad_hist["registry"]["serve_stage_score_us"]["p99"]
    assert any("p99" in p for p in check_registry_section(bad_hist))
    bad_type = json.loads(json.dumps(ok))
    bad_type["registry"]["delta_fill"]["type"] = "dial"
    assert any("unknown" in p for p in check_registry_section(bad_type))
    no_stages = json.loads(json.dumps(ok))
    no_stages["stages"] = {}
    assert any("stages" in p for p in check_registry_section(no_stages))


def test_event_log_capacity_configurable_end_to_end():
    """EventLog capacity is caller-sized, not the hard-coded 256:
    ``resize`` rebounds the ring keeping the newest events (seq and
    per-kind counts survive), ``SegmentedIndex(event_capacity=)`` sizes
    the index's log at construction, and the serving tier plumbs it
    (``ServerConfig.event_capacity`` resizes the served index,
    ``MeshConfig`` inherits it for every replica)."""
    log = EventLog(capacity=4)
    for i in range(6):
        log.emit("seal", epoch=i)
    assert log.capacity == 4 and len(log) == 4
    log.resize(2)                         # shrink keeps the NEWEST
    assert log.capacity == 2
    assert [e["epoch"] for e in log.tail(10)] == [4, 5]
    assert log.total == 6 and log.counts() == {"seal": 6}
    log.resize(8)                         # grow keeps everything held
    log.emit("compact", merged=2)
    assert len(log) == 3
    with pytest.raises(ValueError):
        log.resize(0)

    tc = corpus.generate(corpus.CorpusSpec(num_docs=200, vocab=100,
                                           avg_distinct=10, seed=4))
    si = SegmentedIndex(term_hashes=tc.term_hashes,
                        delta_doc_capacity=200, event_capacity=7)
    assert si.events.capacity == 7

    from repro.serve import MeshConfig, MeshServer, QueryServer, ServerConfig
    si.add_batch(_slice(tc, 0, 200))
    si.seal()
    QueryServer(si, ServerConfig(backend="xla", event_capacity=9))
    assert si.events.capacity == 9
    QueryServer(si, ServerConfig(backend="xla"))     # None leaves it alone
    assert si.events.capacity == 9

    import jax
    ms = MeshServer(si, MeshConfig(batch_size=4, k=10, n_shards=1,
                                   n_replicas=2, auto_handoff=False,
                                   event_capacity=11),
                    mesh=jax.make_mesh((1,), ("shards",)))
    assert all(r.index.events.capacity == 11 for r in ms.replicas)
    ms.stop()


# ---------------------------------------------------------------------------
# profiler annotations, the compile counter, the host overflow branch
# ---------------------------------------------------------------------------

LEAVES = ["assemble", "dispatch", "device_wait", "merge", "result", "fetch",
          "respond"]


def _distinct_queries(tc, n):
    return corpus.sample_query_terms(
        build.bulk_build(_slice(tc, 0, 300)).df, tc.term_hashes, n, 3,
        num_docs=300, seed=5)


def _serve(server, rows):
    tickets = [server.submit(r) for r in rows]
    while server.pending:
        server.pump()
    return [t.result(timeout=120.0) for t in tickets]


def test_traced_batch_annotates_its_leaf_stages(tmp_path):
    """Under the benchmark's profiler options (host level 1, python
    level 0) every scored batch writes one ``serve.<leaf>`` annotation
    per leaf stage, in order, each as long as its span to within 1 ms;
    the leaves never nest, and the typical hole between two of them is
    under 1 ms (a rare scheduler pause may fall between two)."""
    import gc
    import statistics

    tc = _mini_corpus()
    _, server = _make_server(tc, 1, cache_capacity=0)
    server.warmup()
    rows = _distinct_queries(tc, 24)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    gc.disable()
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        responses = _serve(server, rows)
        jax.profiler.stop_trace()
    finally:
        gc.enable()
    profile = jax.profiler.ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb"))))
    notes = sorted((e for p in profile.planes for line in p.lines
                    for e in line.events if e.name.startswith("serve.")),
                   key=lambda e: e.start_ns)

    def first(trace, name):
        return next(s for s in trace.spans if s.name == name)

    # each batch's tickets, grouped by their shared assemble span, in
    # the order the batches were served
    batches = {}
    for r in responses:
        batches.setdefault(id(first(r.trace, "assemble")), []).append(
            r.trace)
    traces = sorted(batches.values(),
                    key=lambda ts: first(ts[0], "assemble").t0)
    assert len(traces) == len(rows) // 4
    assert [e.name for e in notes] == [f"serve.{n}" for n in LEAVES] * len(
        traces)
    holes = {}
    for i, ts in enumerate(traces):
        group = notes[i * len(LEAVES):(i + 1) * len(LEAVES)]
        want = {name: first(ts[0], name).duration_us * 1e3
                for name in LEAVES[:-1]}
        # respond is one span per ticket; the annotation covers them all
        want["respond"] = (max(first(t, "respond").t1 for t in ts)
                           - first(ts[0], "score").t1) * 1e9
        for note, name in zip(group, LEAVES):
            assert abs(note.duration_ns - want[name]) < 1e6, (
                name, note.duration_ns, want[name])
        for a, b in zip(group, group[1:]):
            gap = b.start_ns - (a.start_ns + a.duration_ns)
            assert gap >= 0, (a.name, b.name)
            holes.setdefault((a.name, b.name), []).append(gap)
    for pair, gaps in holes.items():
        assert statistics.median(gaps) < 1e6, (pair, gaps)


def test_disabled_tracing_enters_no_annotation(monkeypatch):
    """trace_sample=0: no Span, no Trace and no profiler annotation."""
    tc = _mini_corpus()
    _, server = _make_server(tc, 0, cache_capacity=0)
    server.warmup()

    def boom(*a, **k):
        raise AssertionError("constructed with tracing disabled")

    monkeypatch.setattr(obs_trace.Span, "__init__", boom)
    monkeypatch.setattr(obs_trace.Trace, "__init__", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    responses = _serve(server, _distinct_queries(tc, 8))
    assert len(responses) == 8 and all(r.ok for r in responses)
    assert all(r.trace is None for r in responses)


def test_serve_compiles_counts_only_compiles_inside_a_batch():
    """A warm server compiles nothing while serving; a batch of a new
    shape counts its compiles; a compile between batches does not
    count."""
    tc = _mini_corpus()
    _, server = _make_server(tc, 0, cache_capacity=0)
    server.warmup()
    compiles = server.registry.get("serve_compiles")
    rows = _distinct_queries(tc, 12)
    _serve(server, rows[:8])
    assert compiles.value == 0
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
    assert compiles.value == 0
    assert server.metrics_snapshot()["serve_compiles"]["value"] == 0
    # a wider term budget is a batch shape no warm-up compiled
    server.config = dataclasses.replace(server.config, n_terms_budget=16)
    _serve(server, rows[8:])
    assert compiles.value > 0


def test_overflow_host_count_prints_and_counts_like_the_jitted_path(capsys):
    """A host int is checked on the host: the same message as the
    jitted branch, and the same ``engine_pair_overflow`` count."""
    c = GLOBAL.counter("engine_pair_overflow")
    before = c.value
    ops.warn_on_overflow(5, "test_obs host")
    host_out = capsys.readouterr().out
    assert c.value == before + 5

    @jax.jit
    def f(o):
        ops.warn_on_overflow(o, "test_obs host")
        return o

    f(jnp.asarray(5, jnp.int32)).block_until_ready()
    jax.effects_barrier()
    assert c.value == before + 10
    assert host_out.strip() == capsys.readouterr().out.strip() == (
        "test_obs host: routing overflow dropped 5 (block, tile) pairs — "
        "raise max_pairs")
    ops.warn_on_overflow(0, "test_obs host zero")
    assert capsys.readouterr().out == "" and c.value == before + 10


def test_avgdl_gauge_and_bm25_batch_counter():
    """The pinned epoch's live tokens and avgdl are gauges set at each
    pin: they equal the index's live tokens over live documents after
    an ingest and after a delete.  Each scored BM25 batch counts once;
    warm-up counts none."""
    from repro.core.ranking import bm25
    tc = corpus.generate(corpus.CorpusSpec(num_docs=160, vocab=200,
                                           avg_distinct=12, seed=21))
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=64,
                        delta_posting_capacity=2048,
                        ranking=bm25(0.9, 0.4))
    si.add_batch(_slice(tc, 0, 100))
    server = QueryServer(si, ServerConfig(batch_size=4, n_terms_budget=4,
                                          k=5, cache_capacity=0))
    qh = corpus.sample_query_terms(np.bincount(np.concatenate(
        tc.doc_term_ids), minlength=200), tc.term_hashes, 6, 3,
        num_docs=tc.num_docs, seed=2)

    def gauges():
        snap = server.metrics_snapshot(include_global=False)
        return (snap["index_live_tokens"]["value"],
                snap["index_avgdl"]["value"],
                snap["serve_bm25_batches"]["value"])

    def tokens():
        live = si.live_mask()
        return sum(int(np.sum(tc.doc_counts[d]))
                   for d in np.flatnonzero(live))

    server.warmup()
    assert gauges() == (tokens(), tokens() / 100, 0)
    si.add_batch(_slice(tc, 100, 160))
    for row in qh:
        server.submit(row)
    assert server.pump(max_batches=10) == len(qh)
    assert gauges() == (tokens(), pytest.approx(tokens() / 160), 2)
    si.delete([5, 120, 150])
    server.submit(qh[0])
    assert server.pump() == 1
    assert si.live_token_count == tokens()
    assert gauges() == (tokens(), pytest.approx(tokens() / 157), 3)
