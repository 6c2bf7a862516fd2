"""Serving subsystem: micro-batched QueryServer, epoch-pinned
snapshots, result cache, background maintenance.

The central contract (the PR's acceptance criterion): under a churn
schedule — adds, deletes, seals, compactions running between/behind
query batches — EVERY response the server returns is bit-identical
(ties included) to the jnp oracle over ``bulk_build`` of the live
corpus AT THE EPOCH the response was pinned to, and steady-state
serving adds ZERO jit cache entries after one warmup per size class.
"""
import os
import tempfile
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import build, compaction, layouts, query
from repro.core import live_index as li
from repro.core.build import TokenizedCorpus
from repro.core.live_index import SegmentedIndex
from repro.serve import (IndexMaintenance, QueryServer, ResultCache,
                         ServerConfig, load_segmented, pin,
                         restore_segmented, save_segmented,
                         serialize_segmented)
from repro.serve.metrics import LatencyWindow, ServerMetrics, percentiles
from repro.text import corpus


def _slices(tc, bounds):
    return [TokenizedCorpus(tc.doc_term_ids[a:b], tc.doc_counts[a:b],
                            tc.term_hashes, b - a)
            for a, b in zip(bounds[:-1], bounds[1:])]


class RecordingServer(QueryServer):
    """QueryServer that remembers every view it pinned, keyed by epoch —
    so a test can oracle-check a response against the exact snapshot it
    was served from, even when maintenance ran in another thread."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.views = {self._pinned.epoch: self._pinned}

    def refresh_view(self):
        v = super().refresh_view()
        self.views[v.epoch] = v
        return v


def _oracle_for_view(view, k):
    """jnp-oracle scorer over bulk_build of the view's live corpus, with
    compact doc ids mapped back to global ids."""
    tc_live, live_ids = view.export_live_corpus()
    host = build.bulk_build(tc_live)
    ix = layouts.build_blocked(host)
    cap = max(host.max_posting_len, 1)
    scorer = query.make_scorer(ix, k=k, cap=cap)

    def run(rows):
        r = scorer(jnp.asarray(rows))
        oid = np.asarray(r.doc_ids)
        mapped = np.where(oid >= 0, live_ids[np.maximum(oid, 0)], -1)
        return mapped.astype(np.int32), np.asarray(r.scores)

    return run


def _check_responses(server, answered, k):
    """Every (ticket, response) pair must match the oracle of its pinned
    epoch bit-identically (ids incl. tie order; scores to float tol)."""
    by_epoch = {}
    for ticket in answered:
        r = ticket.response
        by_epoch.setdefault(r.epoch, []).append(ticket)
    for epoch, tickets in by_epoch.items():
        oracle = _oracle_for_view(server.views[epoch], k)
        rows = np.stack([t.row for t in tickets])
        want_ids, want_scores = oracle(rows)
        for i, t in enumerate(tickets):
            np.testing.assert_array_equal(t.response.doc_ids, want_ids[i])
            np.testing.assert_allclose(t.response.scores, want_scores[i],
                                       rtol=1e-5, atol=1e-7)


def test_server_parity_and_zero_recompiles_under_churn():
    """The acceptance criterion: a 64-batch query stream interleaved
    with add/delete/seal/compact maintenance — every response matches
    the oracle at its pinned epoch, zero new jit entries after warmup,
    and the cache serves hits at stable epochs."""
    rng = np.random.default_rng(0)
    tc = corpus.generate(corpus.CorpusSpec(num_docs=1600, vocab=400,
                                           avg_distinct=16, seed=4))
    B = 64
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=B,
                        delta_posting_capacity=B * 40,
                        policy=compaction.TieredPolicy(size_ratio=4.0,
                                                       min_run=4))
    cfg = ServerConfig(batch_size=8, n_terms_budget=8, k=10)
    server = RecordingServer(si, cfg)
    maint = IndexMaintenance(si, server.index_lock, seal_fill=0.9)
    pool = corpus.sample_query_terms(
        build.bulk_build(_slices(tc, [0, 200])[0]).df, tc.term_hashes,
        24, 3, num_docs=200, seed=5)

    def submit_and_pump(n):
        tickets = [server.submit(pool[rng.integers(len(pool))])
                   for _ in range(n)]
        while server.pending:
            server.pump()
        return tickets

    # -- warmup: mint the schedule's size classes (delta seals + an
    # L1 compaction + deletes), serving all the while
    answered = []
    a = 0
    for _ in range(6):
        with server.index_lock:
            si.add_batch(_slices(tc, [a, a + B])[0])
        a += B
        maint.run_once()
        answered += submit_and_pump(8)
    with server.index_lock:
        si.delete([a - 1, a - 5])
    server.warmup()
    answered += submit_and_pump(8)
    assert si.stats.compactions >= 1
    snap = li.scorer_cache_sizes()

    # -- the measured stream: 64 micro-batches under churn.  Ingest is
    # paced so compactions stay within the size classes warmup minted
    # (the zero-recompile contract is per warm class, as in the PR-3
    # churn test; a deeper LSM cascade would legitimately mint new
    # classes — that log-bounded growth is pinned by the slow sweep)
    for step in range(64):
        if step % 8 == 1:
            with server.index_lock:
                si.add_batch(_slices(tc, [a, a + B])[0])
            a += B
        if step % 8 == 3:
            live = np.flatnonzero(si.live_mask())
            with server.index_lock:
                si.delete(rng.choice(live, size=5, replace=False))
        if step % 2 == 0:
            maint.run_once()
        answered += submit_and_pump(cfg.batch_size)

    assert li.scorer_cache_sizes() == snap, "serving minted new jit entries"
    assert maint.stats.seals >= 1          # maintenance did real sealing
    assert si.stats.seals >= 6
    assert si.stats.compactions >= 2
    _check_responses(server, answered, cfg.k)
    # the finite pool + stable epochs between mutations => real hits
    assert server.cache.hits > 0
    s = server.metrics.summary()
    assert s["requests"] == len(answered)
    assert s["epochs_served"] >= 3
    assert s["p99_us"] >= s["p50_us"] > 0


def test_server_parity_with_background_threads():
    """Randomized interleave with REAL threads: worker + maintenance +
    an ingest thread race; every response still matches the oracle of
    its pinned epoch (consistency comes from the pin, not from
    scheduling luck)."""
    tc = corpus.generate(corpus.CorpusSpec(num_docs=900, vocab=300,
                                           avg_distinct=14, seed=7))
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=48,
                        delta_posting_capacity=2048,
                        policy=compaction.TieredPolicy(size_ratio=4.0,
                                                       min_run=3))
    si.add_batch(_slices(tc, [0, 300])[0])
    cfg = ServerConfig(batch_size=4, n_terms_budget=8, k=10)
    server = RecordingServer(si, cfg)
    maint = IndexMaintenance(si, server.index_lock, seal_fill=0.5,
                             interval_s=0.001)
    server.warmup()
    pool = corpus.sample_query_terms(np.asarray(si._df), si.term_hashes,
                                     16, 3, num_docs=si.live_doc_count,
                                     seed=2)
    rng = np.random.default_rng(3)

    def ingest():
        # free-running writer racing the worker + maintenance threads
        for a in range(300, 600, 60):
            with server.index_lock:
                si.add_batch(_slices(tc, [a, a + 60])[0])
                if a % 120 == 0:
                    si.delete([a - 3, a - 11])

    server.start()
    maint.start()
    ingester = threading.Thread(target=ingest, daemon=True)
    ingester.start()
    # waves: each waits for its responses, with an ingest between waves
    # (so >= 2 distinct epochs are served no matter how the free-running
    # threads happen to schedule)
    tickets = []
    wave_starts = list(range(600, 900, 60))
    for wave in range(6):
        batch = [server.submit(pool[rng.integers(len(pool))])
                 for _ in range(16)]
        for t in batch:
            t.result(timeout=300.0)
        tickets += batch
        if wave < len(wave_starts):
            a = wave_starts[wave]
            with server.index_lock:
                si.add_batch(_slices(tc, [a, a + 60])[0])
    ingester.join(timeout=300.0)
    maint.stop()
    server.stop()
    responses = [t.response for t in tickets]
    assert all(r is not None for r in responses)
    assert len({r.epoch for r in responses}) >= 2
    _check_responses(server, tickets, cfg.k)


def test_pinned_view_is_immutable_under_mutation():
    """A pinned view keeps answering for ITS epoch after the live index
    moves on — deletes and compactions land only in newer epochs."""
    tc = corpus.generate(corpus.CorpusSpec(num_docs=300, vocab=250,
                                           avg_distinct=15, seed=3))
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=64,
                        delta_posting_capacity=4096,
                        policy=compaction.TieredPolicy(min_run=100))
    si.add_batch(_slices(tc, [0, 200])[0])
    si.seal()
    qh = corpus.sample_query_terms(np.asarray(si._df), si.term_hashes,
                                   4, 3, num_docs=si.live_doc_count, seed=2)
    view = pin(si)
    before = view.topk(qh, k=10)
    winner = int(np.asarray(before.doc_ids)[0, 0])
    # mutate: delete the winner, add docs, compact
    si.delete([winner])
    si.add_batch(_slices(tc, [200, 300])[0])
    si.seal()
    si.compact(all_segments=True)
    assert si.epoch > view.epoch
    again = view.topk(qh, k=10)
    np.testing.assert_array_equal(np.asarray(again.doc_ids),
                                  np.asarray(before.doc_ids))
    np.testing.assert_array_equal(np.asarray(again.scores),
                                  np.asarray(before.scores))
    # and the pinned view still matches the oracle OF ITS EPOCH
    oracle = _oracle_for_view(view, 10)
    want_ids, want_scores = oracle(qh.astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(again.doc_ids), want_ids)
    # while the live index has genuinely moved on
    now_ids = np.asarray(si.topk(qh, k=10).doc_ids)
    assert winner not in now_ids[now_ids >= 0]


@pytest.mark.parametrize("seal_layout", ["hor", "packed"])
def test_snapshot_restore_bit_identical(seal_layout):
    """serialize -> restore (and save -> load through a file) answers
    bit-identically, keeps stats/policy/rng, and stays bit-identical
    under identical FUTURE mutation schedules (rng state rides along)."""
    tc = corpus.generate(corpus.CorpusSpec(num_docs=400, vocab=250,
                                           avg_distinct=14, seed=9))
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=48,
                        delta_posting_capacity=2048,
                        policy=compaction.TieredPolicy(size_ratio=4.0,
                                                       min_run=3),
                        seal_layout=seal_layout)
    for a in range(0, 300, 60):
        si.add_batch(_slices(tc, [a, a + 60])[0])
    si.delete([3, 77, 150])
    # vocab growth after some segments sealed (restore must rebuild old
    # segments against the GROWN vocabulary and still answer identically)
    extra = TokenizedCorpus(
        doc_term_ids=[np.asarray([0, 1], np.int64)],
        doc_counts=[np.asarray([2, 1], np.int64)],
        term_hashes=np.array([0xDEADBEEF, 0xFEEDFACE], np.uint32),
        num_docs=1)
    si.add_batch(extra)
    qh = corpus.sample_query_terms(np.asarray(si._df)[:250],
                                   si.term_hashes[:250], 6, 3,
                                   num_docs=si.live_doc_count, seed=2)

    state = serialize_segmented(si, lock=threading.RLock())
    si2 = restore_segmented(state)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "snap.npz")
        save_segmented(si, path)
        si3 = load_segmented(path)

    for other in (si2, si3):
        assert other.epoch == si.epoch
        assert other.num_segments == si.num_segments
        assert other.live_doc_count == si.live_doc_count
        np.testing.assert_array_equal(other._df, si._df)
        np.testing.assert_array_equal(other._doc_row, si._doc_row)
        r1 = si.topk(qh, k=10)
        r2 = other.topk(qh, k=10)
        np.testing.assert_array_equal(np.asarray(r1.doc_ids),
                                      np.asarray(r2.doc_ids))
        np.testing.assert_array_equal(np.asarray(r1.scores),
                                      np.asarray(r2.scores))
    # restored index matches the oracle too (not just the original)
    oracle = _oracle_for_view(si2.view(), 10)
    want_ids, _ = oracle(qh.astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(si2.topk(qh, k=10).doc_ids),
                                  want_ids)
    # identical future mutations stay bit-identical (rng state restored)
    for target in (si, si2):
        target.add_batch(_slices(tc, [300, 400])[0])
        target.delete([301])
    r1, r2 = si.topk(qh, k=10), si2.topk(qh, k=10)
    np.testing.assert_array_equal(np.asarray(r1.doc_ids),
                                  np.asarray(r2.doc_ids))
    np.testing.assert_array_equal(np.asarray(r1.scores),
                                  np.asarray(r2.scores))


def test_result_cache_semantics():
    """LRU bound, epoch keying, purge, hit accounting."""
    c = ResultCache(capacity=2)
    row = np.array([1, 2, 0], np.uint32)
    k1 = c.make_key(row, 10, epoch=1)
    assert c.get(k1) is None and c.misses == 1
    c.put(k1, np.array([5, -1]), np.array([0.5, 0.0]))
    ids, scores = c.get(k1)
    np.testing.assert_array_equal(ids, [5, -1])
    assert c.hits == 1
    # mutating what the caller got back must not poison the cache
    ids[0] = 99
    np.testing.assert_array_equal(c.get(k1)[0], [5, -1])
    # same query at a newer epoch is a different key
    k2 = c.make_key(row, 10, epoch=2)
    assert c.get(k2) is None
    c.put(k2, np.array([6]), np.array([0.1]))
    # LRU bound: k1 was most recently touched via get, so adding a third
    # entry evicts the oldest-touched
    k3 = c.make_key(row, 5, epoch=2)
    c.put(k3, np.array([7]), np.array([0.2]))
    assert len(c) == 2
    # purge_below removes stale-epoch entries
    c.put(k2, np.array([6]), np.array([0.1]))
    assert c.purge_below(2) >= 0
    assert all(key[2] >= 2 for key in c._store)
    assert 0.0 < c.hit_rate < 1.0
    c.reset_counters()
    assert c.hits == c.misses == 0


def test_server_cache_hits_are_bit_identical_and_epoch_scoped():
    tc = corpus.generate(corpus.CorpusSpec(num_docs=200, vocab=200,
                                           avg_distinct=12, seed=6))
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=64,
                        delta_posting_capacity=4096)
    si.add_batch(_slices(tc, [0, 150])[0])
    server = QueryServer(si, ServerConfig(batch_size=4, n_terms_budget=6,
                                          k=8))
    server.warmup()
    qh = corpus.sample_query_terms(np.asarray(si._df), si.term_hashes,
                                   1, 3, num_docs=si.live_doc_count,
                                   seed=1)[0]
    r1 = server.query(qh)
    r2 = server.query(qh)
    assert not r1.cached and r2.cached
    assert r1.epoch == r2.epoch
    np.testing.assert_array_equal(r1.doc_ids, r2.doc_ids)
    np.testing.assert_array_equal(r1.scores, r2.scores)
    # epoch advance invalidates: the winner is deleted, a fresh (not
    # cached) response excludes it
    winner = int(r1.doc_ids[0])
    with server.index_lock:
        si.delete([winner])
    r3 = server.query(qh)
    assert not r3.cached and r3.epoch > r1.epoch
    assert winner not in r3.doc_ids[r3.doc_ids >= 0]
    # overwide queries are rejected, never truncated
    with pytest.raises(ValueError):
        server.submit(np.arange(1, 8, dtype=np.uint32))
    # and so are batches: submit takes ONE query, never flattens [B, T]
    with pytest.raises(ValueError, match="ONE query"):
        server.submit(np.ones((2, 3), np.uint32))


def test_metrics_percentiles_and_window():
    samples = [10.0, 20.0, 30.0, 40.0, 100.0]
    p = percentiles(samples, (50, 99))
    assert p["p50"] == pytest.approx(np.percentile(samples, 50))
    assert p["p99"] == pytest.approx(np.percentile(samples, 99))
    assert percentiles([], (50, 99)) == {"p50": 0.0, "p99": 0.0}
    w = LatencyWindow()
    for s in samples:
        w.record(s)
    out = w.summary()
    assert out["count"] == 5
    assert out["p50_us"] == pytest.approx(30.0)
    assert out["mean_us"] == pytest.approx(40.0)
    assert out["qps"] >= 0.0
    m = ServerMetrics()
    m.batched_queries, m.padded_slots = 6, 2
    assert m.batch_fill() == pytest.approx(0.75)
    m.observe_epoch(3)
    m.observe_epoch(3)
    m.observe_epoch(4)
    assert m.epochs_served == 2
    m.reset()
    assert m.epochs_served == 0 and m.batch_fill() == 0.0


def test_maintenance_triggers_and_stats():
    """Seal fires on delta fill, compaction on the policy trigger; an
    idle index is a no-op without taking work."""
    tc = corpus.generate(corpus.CorpusSpec(num_docs=300, vocab=200,
                                           avg_distinct=12, seed=8))
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=100,
                        delta_posting_capacity=8192,
                        policy=compaction.TieredPolicy(size_ratio=4.0,
                                                       min_run=3))
    lock = threading.RLock()
    maint = IndexMaintenance(si, lock, seal_fill=0.5,
                             max_compactions_per_run=4)
    assert maint.run_once() == {"sealed": False, "compacted": 0,
                                "rewritten": 0}
    si.add_batch(_slices(tc, [0, 60])[0])        # fill 0.6 >= 0.5
    did = maint.run_once()
    assert did["sealed"] and si.num_segments == 1
    assert si.delta_fill == 0.0
    # three more delta-sized runs -> policy merges on the next run
    for a in range(60, 240, 60):
        si.add_batch(_slices(tc, [a, a + 60])[0])
        maint.run_once()
    assert maint.stats.seals >= 3
    assert si.stats.compactions >= 1
    # quiescent: nothing due, nothing done
    before = (maint.stats.seals, maint.stats.compactions)
    assert maint.run_once() == {"sealed": False, "compacted": 0,
                                "rewritten": 0}
    assert (maint.stats.seals, maint.stats.compactions) == before
    # thread start/stop is clean and idempotent
    maint.start()
    maint.start()
    maint.stop()


def test_sharded_stack_from_pinned_view_requires_sealed_delta():
    tc = corpus.generate(corpus.CorpusSpec(num_docs=200, vocab=200,
                                           avg_distinct=12, seed=5))
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=64,
                        delta_posting_capacity=4096)
    si.add_batch(_slices(tc, [0, 150])[0])
    from repro.distributed import retrieval
    with pytest.raises(ValueError, match="seal"):
        retrieval.stack_segment_shards(pin(si), 2)
    si.seal()
    # packed stacks are first-class now (the former HOR-only ValueError
    # is gone): the builder buckets them into packed-layout groups
    si2 = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=64,
                         delta_posting_capacity=4096, seal_layout="packed")
    si2.add_batch(_slices(tc, [0, 150])[0])
    si2.seal()
    stacks = retrieval.stack_segment_shards(si2, 2)
    assert {m.layout for m, _ in stacks.groups} == {"packed"}


def test_server_over_packed_sharded_stack_under_ingest():
    """Serving-tier regression for the packed distributed tier: a
    sharded stack built from a pinned epoch of a PACKED index answers
    bit-identically to that epoch's oracle (and to the QueryServer
    responses pinned to it) while ingest keeps landing afterwards."""
    import jax
    from repro.distributed import retrieval

    tc = corpus.generate(corpus.CorpusSpec(num_docs=600, vocab=300,
                                           avg_distinct=14, seed=21))
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=64,
                        delta_posting_capacity=4096,
                        policy=compaction.TieredPolicy(min_run=100),
                        seal_layout="packed")
    si.add_batch(_slices(tc, [0, 300])[0])
    cfg = ServerConfig(batch_size=4, n_terms_budget=8, k=10)
    server = RecordingServer(si, cfg)
    server.warmup()
    pool = corpus.sample_query_terms(np.asarray(si._df), si.term_hashes,
                                     8, 3, num_docs=si.live_doc_count,
                                     seed=4)

    # pin a consistent epoch with a sealed delta, then build the sharded
    # serving stack FROM THE PIN while the writer keeps mutating
    with server.index_lock:
        si.seal()
        view = pin(si)
    mesh = jax.make_mesh((1,), ("data",))
    stacks = retrieval.stack_segment_shards(view, 1)
    assert {m.layout for m, _ in stacks.groups} == {"packed"}
    scorer = retrieval.make_doc_sharded_segment_scorer(stacks, mesh,
                                                       "data", k=cfg.k)

    # concurrent ingest: later epochs must not leak into the stack
    with server.index_lock:
        si.add_batch(_slices(tc, [300, 450])[0])
        si.delete([5, 17])
    tickets = [server.submit(q) for q in pool]
    while server.pending:
        server.pump()

    oracle = _oracle_for_view(view, cfg.k)
    want_ids, want_scores = oracle(pool.astype(np.uint32))
    for i, q in enumerate(pool):
        vv, ids = scorer(np.asarray(q, np.uint32))
        hit = np.isfinite(np.asarray(vv))
        np.testing.assert_array_equal(
            np.where(hit, np.asarray(ids), -1), want_ids[i])
        np.testing.assert_allclose(np.asarray(vv)[hit],
                                   want_scores[i][hit], rtol=1e-5,
                                   atol=1e-7)
    # and the server's responses are themselves oracle-exact at their
    # (newer) pinned epochs — serving never regressed while the stack
    # stayed consistent at ITS epoch
    _check_responses(server, tickets, cfg.k)


def test_snapshot_restore_mixed_layout_bitwise():
    """A MIXED hor+packed stack (per-seal layout overrides) round-trips
    through serialize/restore with each segment in its original layout,
    answers bit-identically, and stays bit-identical under identical
    future mutations."""
    from repro.core.layouts import BlockedIndex, PackedCsrIndex

    tc = corpus.generate(corpus.CorpusSpec(num_docs=400, vocab=250,
                                           avg_distinct=14, seed=31))
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=96,
                        delta_posting_capacity=8192,
                        policy=compaction.TieredPolicy(min_run=100))
    for i, a in enumerate(range(0, 300, 75)):
        si.add_batch(_slices(tc, [a, a + 75])[0])
        si.seal(layout="packed" if i % 2 else "hor")
    si.delete([8, 120, 260])
    want_layouts = [s.layout for s in si.segments()]
    assert set(want_layouts) == {"hor", "packed"}

    state = serialize_segmented(si, lock=threading.RLock())
    si2 = restore_segmented(state)
    # structural roundtrip: every segment restored in its ORIGINAL
    # layout (not the index-wide default)
    assert [s.layout for s in si2.segments()] == want_layouts
    for s1, s2 in zip(si.segments(), si2.segments()):
        assert type(s1.index) is type(s2.index)
        if isinstance(s1.index, PackedCsrIndex):
            np.testing.assert_array_equal(np.asarray(s1.index.packed),
                                          np.asarray(s2.index.packed))
        else:
            assert isinstance(s1.index, BlockedIndex)
            np.testing.assert_array_equal(np.asarray(s1.index.block_docs),
                                          np.asarray(s2.index.block_docs))
    qh = corpus.sample_query_terms(np.asarray(si._df), si.term_hashes,
                                   6, 3, num_docs=si.live_doc_count,
                                   seed=2)
    r1, r2 = si.topk(qh, k=10), si2.topk(qh, k=10)
    np.testing.assert_array_equal(np.asarray(r1.doc_ids),
                                  np.asarray(r2.doc_ids))
    np.testing.assert_array_equal(np.asarray(r1.scores),
                                  np.asarray(r2.scores))
    # identical future mutations (incl. a packed seal) stay bitwise
    for target in (si, si2):
        target.add_batch(_slices(tc, [300, 400])[0])
        target.seal(layout="packed")
        target.delete([301])
    r1, r2 = si.topk(qh, k=10), si2.topk(qh, k=10)
    np.testing.assert_array_equal(np.asarray(r1.doc_ids),
                                  np.asarray(r2.doc_ids))
    np.testing.assert_array_equal(np.asarray(r1.scores),
                                  np.asarray(r2.scores))


@pytest.mark.slow
def test_serving_benchmark_long_sweep():
    """The daily-suite QPS sweep: more rates and requests than the
    PR-gating smoke, through the real threaded server + maintenance."""
    from benchmarks import common, serving
    tc = corpus.generate(corpus.CorpusSpec(num_docs=1500, vocab=600,
                                           avg_distinct=25, seed=42))
    host = build.bulk_build(tc)
    results = serving.run_sweep([25, 100, 400], 192, tc=tc, host=host)
    rates = [s["offered_qps"] for s in results if "offered_qps" in s]
    assert rates == [25, 100, 400]
    for s in results:
        if "offered_qps" not in s:
            continue
        assert s["requests"] == 192
        assert s["p99_us"] >= s["p50_us"] > 0
        assert 0.0 <= s["cache_hit_rate"] <= 1.0
        assert common.latency_summary(s["samples_us"]).startswith("p50=")
    lifecycle = results[-1]["lifecycle"]
    assert lifecycle["epoch"] > 0
