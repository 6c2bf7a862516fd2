"""Segmented live index: LSM ingest, tombstones, compaction, parity.

The central contract: at ANY point of an add/delete/compact schedule,
``SegmentedIndex.topk`` (fused pallas candidates engine, the default)
is bit-identical — ties included — to the jnp oracle over
``bulk_build`` of the equivalent live corpus.  Plus: delete semantics
end-to-end across every engine, multi-segment conjunctive stats
aggregation, the recompile-avoidance contract under churn, and the
posting-merge work advantage over the rebuild path.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import build, compaction, layouts, query
from repro.core import live_index as li
from repro.core.build import TokenizedCorpus
from repro.core.live_index import SegmentedIndex
from repro.text import corpus

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _slices(tc, bounds):
    return [TokenizedCorpus(tc.doc_term_ids[a:b], tc.doc_counts[a:b],
                            tc.term_hashes, b - a)
            for a, b in zip(bounds[:-1], bounds[1:])]


def _oracle_topk(si, qh, k):
    """jnp oracle over bulk_build of the equivalent live corpus, with
    its compact doc ids mapped back to the live index's global ids."""
    tc_live, live_ids = si.export_live_corpus()
    if tc_live.num_docs == 0:
        shape = (np.asarray(qh).shape[0], k)
        return np.full(shape, -1, np.int32), np.zeros(shape, np.float32)
    host = build.bulk_build(tc_live)
    ix = layouts.build_blocked(host)
    cap = max(host.max_posting_len, 1)
    r = query.make_scorer(ix, k=k, cap=cap)(jnp.asarray(qh))
    oid = np.asarray(r.doc_ids)
    mapped = np.where(oid >= 0, live_ids[np.maximum(oid, 0)], -1)
    return mapped.astype(np.int32), np.asarray(r.scores)


def _assert_live_parity(si, qh, k=10, **topk_kw):
    want_ids, want_scores = _oracle_topk(si, qh, k)
    got = si.topk(qh, k=k, **topk_kw)
    np.testing.assert_array_equal(np.asarray(got.doc_ids), want_ids)
    np.testing.assert_allclose(np.asarray(got.scores), want_scores,
                               rtol=1e-5, atol=1e-7)


def test_randomized_schedule_parity_every_step():
    """Randomized add/delete/compact schedule: fused multi-segment top-k
    equals the rebuild oracle at EVERY step (the acceptance criterion)."""
    rng = np.random.default_rng(0)
    tc = corpus.generate(corpus.CorpusSpec(num_docs=360, vocab=300,
                                           avg_distinct=18, seed=11))
    batches = _slices(tc, [0, 60, 110, 180, 240, 300, 360])
    si = SegmentedIndex(term_hashes=tc.term_hashes,
                        delta_doc_capacity=48,
                        delta_posting_capacity=2048,
                        policy=compaction.TieredPolicy(size_ratio=4.0,
                                                       min_run=3))
    qh = corpus.sample_query_terms(build.bulk_build(tc).df, tc.term_hashes,
                                   3, 3, num_docs=tc.num_docs, seed=5)
    for step, b in enumerate(batches):
        si.add_batch(b)
        if step >= 1:
            live = np.flatnonzero(si.live_mask())
            kill = rng.choice(live, size=min(7, len(live)), replace=False)
            si.delete(kill)
        if step == 3:
            si.compact(all_segments=True)
        _assert_live_parity(si, qh, k=10)
    assert si.stats.seals > 0 and si.stats.compactions > 0
    assert si.stats.deletes > 0


def test_engines_agree_and_make_scorer_dispatch():
    tc = corpus.generate(corpus.CorpusSpec(num_docs=200, vocab=250,
                                           avg_distinct=15, seed=3))
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=48,
                        delta_posting_capacity=2048)
    si.add_batch(_slices(tc, [0, 200])[0])
    si.delete([5, 9])
    qh = corpus.sample_query_terms(np.asarray(si._df), si.term_hashes,
                                   3, 3, num_docs=si.live_doc_count,
                                   seed=2)
    want_ids, want_scores = _oracle_topk(si, qh, 10)
    for kw in (dict(engine="pallas", mode="candidates"),
               dict(engine="pallas", mode="dense"),
               dict(engine="jnp")):
        got = si.topk(qh, k=10, **kw)
        np.testing.assert_array_equal(np.asarray(got.doc_ids), want_ids)
        np.testing.assert_allclose(np.asarray(got.scores), want_scores,
                                   rtol=1e-5, atol=1e-7)
    # make_scorer dispatches a SegmentedIndex to the live path
    scorer = query.make_scorer(si, k=10, cap=None, engine="pallas")
    got = scorer(qh)
    np.testing.assert_array_equal(np.asarray(got.doc_ids), want_ids)
    with pytest.raises(ValueError):
        si.topk(qh, k=10, engine="cuda")


def _handmade_corpus(term_ids, counts, vocab=32):
    hashes = (np.arange(1, vocab + 1, dtype=np.uint32) * 2654435761
              ).astype(np.uint32)
    return TokenizedCorpus(
        doc_term_ids=[np.asarray(t, np.int64) for t in term_ids],
        doc_counts=[np.asarray(c, np.int64) for c in counts],
        term_hashes=hashes, num_docs=len(term_ids)), hashes


def test_delete_semantics_all_engines_and_readd():
    """Tombstoned docs never surface from any engine; a doc deleted and
    re-added with different content surfaces only as its new id with
    the new content."""
    tc1, hashes = _handmade_corpus(
        term_ids=[[0, 1], [0, 2], [1, 2], [0, 1, 2]],
        counts=[[3, 1], [2, 2], [1, 4], [1, 1, 1]])
    si = SegmentedIndex(term_hashes=hashes, delta_doc_capacity=4,
                        delta_posting_capacity=64,
                        policy=compaction.TieredPolicy(min_run=100))
    si.add_batch(tc1)            # fills delta exactly -> docs 0..3
    qh = np.zeros((1, 3), np.uint32)
    qh[0, 0] = hashes[0]
    top = si.topk(qh, k=4)
    winner = int(np.asarray(top.doc_ids)[0, 0])
    si.delete([winner])
    # re-add "the same document" with DIFFERENT content (term 3 only)
    tc2 = TokenizedCorpus(doc_term_ids=[np.asarray([3], np.int64)],
                          doc_counts=[np.asarray([5], np.int64)],
                          term_hashes=hashes, num_docs=1)
    si.add_batch(tc2)
    new_id = si.num_docs - 1
    for kw in (dict(engine="pallas", mode="candidates"),
               dict(engine="pallas", mode="dense"),
               dict(engine="jnp")):
        ids = np.asarray(si.topk(qh, k=4, **kw).doc_ids)
        assert winner not in ids[ids >= 0], kw
        _assert_live_parity(si, qh, k=4, **kw)
    # old content never matches; new content matches only the new id
    qh3 = np.zeros((1, 3), np.uint32)
    qh3[0, 0] = hashes[3]
    ids3 = np.asarray(si.topk(qh3, k=4).doc_ids)
    assert new_id in ids3[ids3 >= 0]
    assert winner not in ids3[ids3 >= 0]
    # the same holds after seal + compaction; the tombstoned doc's
    # postings are physically gone (store holds live postings only)
    si.seal()
    si.compact(all_segments=True)
    tc_live, _ = si.export_live_corpus()
    live_postings = int(sum(len(t) for t in tc_live.doc_term_ids))
    assert sum(si.segment_postings()) == live_postings
    assert si.delta_postings == 0
    ids = np.asarray(si.topk(qh, k=4).doc_ids)
    assert winner not in ids[ids >= 0]
    _assert_live_parity(si, qh, k=4)


def test_conjunctive_truncation_aggregates_across_segments():
    """A term whose posting list exceeds ``cap`` in an EARLY segment is
    counted even when the last segment scored has no truncation (the
    stats-plumbing fix)."""
    # segment 1: term 0 in 12 docs (> cap), term 1 in 6 (< cap);
    # segment 2: both terms in 2 docs
    tc1, hashes = _handmade_corpus(
        term_ids=[[0, 1]] * 6 + [[0]] * 6,
        counts=[[2, 1]] * 6 + [[2]] * 6)
    si = SegmentedIndex(term_hashes=hashes, delta_doc_capacity=16,
                        delta_posting_capacity=256,
                        policy=compaction.TieredPolicy(min_run=100))
    si.add_batch(tc1)
    si.seal()
    tc2 = TokenizedCorpus(
        doc_term_ids=[np.asarray([0, 1], np.int64)] * 2,
        doc_counts=[np.asarray([1, 1], np.int64)] * 2,
        term_hashes=hashes, num_docs=2)
    si.add_batch(tc2)
    si.seal()
    assert si.num_segments == 2
    qh = np.zeros(3, np.uint32)
    qh[0], qh[1] = hashes[0], hashes[1]
    # cap 8 < 12: only the FIRST segment truncates term 0
    _, stats = si.conjunctive(qh, k=5, cap=8)
    assert stats["truncated_terms"] == 1
    # cap above every local df: exact AND, no truncation, and results
    # match the single-index conjunctive over the rebuilt corpus
    r, stats = si.conjunctive(qh, k=5, cap=16)
    assert stats["truncated_terms"] == 0
    tc_live, live_ids = si.export_live_corpus()
    host = build.bulk_build(tc_live)
    ix = layouts.build_blocked(host)
    ref, ref_stats = query.conjunctive_filter(ix, jnp.asarray(qh), k=5,
                                              cap=16)
    rid = np.asarray(ref.doc_ids)
    mapped = np.where(rid >= 0, live_ids[np.maximum(rid, 0)], -1)
    np.testing.assert_array_equal(np.asarray(r.doc_ids), mapped)
    np.testing.assert_allclose(np.asarray(r.scores),
                               np.asarray(ref.scores), rtol=1e-5)
    assert int(ref_stats["truncated_terms"]) == 0


def test_churn_no_new_compilations_after_warmup():
    """The recompile-avoidance contract: after one warmup per size
    class, further seals, compactions (same classes), deletes, and
    queries add ZERO jit-cache entries."""
    tc = corpus.generate(corpus.CorpusSpec(num_docs=1600, vocab=500,
                                           avg_distinct=18, seed=4))
    B = 64
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=B,
                        delta_posting_capacity=B * 40,
                        policy=compaction.TieredPolicy(size_ratio=4.0,
                                                       min_run=4))
    qh = corpus.sample_query_terms(
        build.bulk_build(_slices(tc, [0, 200])[0]).df, tc.term_hashes,
        4, 3, num_docs=200, seed=5)

    def one_round(a):
        si.add_batch(_slices(tc, [a, a + B])[0])
        si.topk(qh, k=10)
        si.topk(qh, k=10, engine="jnp")
        si.conjunctive(qh[0], k=10, cap=512)

    # warmup: several delta-class seals + one L1-class compaction + a
    # delete, with every engine queried
    step = 0
    for a in range(0, 6 * B, B):
        one_round(a)
        step = a + B
    si.delete([step - 1])
    si.topk(qh, k=10)
    assert si.stats.compactions >= 1
    snap = li.scorer_cache_sizes()

    # churn: six more seals, another same-class compaction, deletes,
    # queries — the jit caches must not grow
    for a in range(step, step + 6 * B, B):
        si.add_batch(_slices(tc, [a, a + B])[0])
        si.delete([a + 3])
        si.topk(qh, k=10)
        si.topk(qh, k=10, engine="jnp")
        si.conjunctive(qh[0], k=10, cap=512)
    assert si.stats.compactions >= 2
    assert li.scorer_cache_sizes() == snap


def test_ingest_merge_work_at_least_10x_below_rebuild():
    """Sustained ingest: posting-merge work per batch (postings touched
    by sort/merge) is >= 10x below the rebuild path's in steady state."""
    tc = corpus.generate(corpus.CorpusSpec(num_docs=3200, vocab=400,
                                           avg_distinct=16, seed=8))
    n_batches = 64
    bounds = np.linspace(0, tc.num_docs, n_batches + 1).astype(int)
    batches = _slices(tc, bounds)
    per = batches[0].num_docs
    si = SegmentedIndex(term_hashes=tc.term_hashes,
                        delta_doc_capacity=per,
                        delta_posting_capacity=per * 40,
                        policy=compaction.TieredPolicy(size_ratio=8.0,
                                                       min_run=8))
    rebuild_touched = 0
    total_postings = 0
    for b in batches:
        si.add_batch(b)
        total_postings += int(sum(len(x) for x in b.doc_term_ids))
        rebuild_touched += total_postings   # the rebuild re-sorts ALL
    live_per_batch = si.stats.postings_merged / n_batches
    steady = total_postings / max(live_per_batch, 1)
    cumulative = rebuild_touched / max(si.stats.postings_merged, 1)
    assert steady >= 10.0, (steady, si.stats)
    assert cumulative >= 5.0, (cumulative, si.stats)
    # each posting was appended exactly once
    assert si.stats.postings_appended == total_postings


def test_packed_seal_layout_parity():
    """seal(layout="packed"): delta+bit-packed sealed segments answer
    bit-identically to the oracle across a randomized add/delete/compact
    schedule, agree with the HOR seal of the same schedule, and mix into
    an HOR stack via the per-seal override."""
    rng = np.random.default_rng(1)
    tc = corpus.generate(corpus.CorpusSpec(num_docs=360, vocab=300,
                                           avg_distinct=18, seed=11))
    batches = _slices(tc, [0, 60, 110, 180, 240, 300, 360])
    kw = dict(delta_doc_capacity=48, delta_posting_capacity=2048,
              policy=compaction.TieredPolicy(size_ratio=4.0, min_run=3))
    si_p = SegmentedIndex(term_hashes=tc.term_hashes, seal_layout="packed",
                          **kw)
    si_h = SegmentedIndex(term_hashes=tc.term_hashes, **kw)
    qh = corpus.sample_query_terms(build.bulk_build(tc).df, tc.term_hashes,
                                   3, 3, num_docs=tc.num_docs, seed=5)
    for step, b in enumerate(batches):
        si_p.add_batch(b)
        si_h.add_batch(b)
        if step >= 1:
            live = np.flatnonzero(si_p.live_mask())
            kill = rng.choice(live, size=min(7, len(live)), replace=False)
            si_p.delete(kill)
            si_h.delete(kill)
        if step == 3:
            si_p.compact(all_segments=True)
            si_h.compact(all_segments=True)
        _assert_live_parity(si_p, qh, k=10)
        got_p = si_p.topk(qh, k=10)
        got_h = si_h.topk(qh, k=10)
        np.testing.assert_array_equal(np.asarray(got_p.doc_ids),
                                      np.asarray(got_h.doc_ids))
        np.testing.assert_allclose(np.asarray(got_p.scores),
                                   np.asarray(got_h.scores), rtol=1e-5)
    assert si_p.stats.seals > 0 and si_p.stats.compactions > 0
    from repro.core.layouts import PackedCsrIndex
    assert all(isinstance(s.index, PackedCsrIndex)
               for s in si_p.segments())
    # mixed stack: one packed seal inside an otherwise-HOR index
    assert si_h.delta_postings > 0     # schedule leaves a partial delta
    si_h.seal(layout="packed")
    layouts_seen = {type(s.index).__name__ for s in si_h.segments()}
    assert layouts_seen == {"BlockedIndex", "PackedCsrIndex"}
    _assert_live_parity(si_h, qh, k=10)
    # the jnp engine agrees over packed segments too
    _assert_live_parity(si_p, qh, k=10, engine="jnp")


def test_pick_compaction_policy():
    """Size-tiered trigger: merges the newest similar-sized run, leaves
    graduated runs alone until enough peers accumulate."""
    pick = compaction.pick_compaction
    assert pick([10, 10, 10, 10], 4.0, 4) == (0, 4)
    assert pick([100, 10, 10, 10, 10], 4.0, 4) == (1, 5)     # big stays
    assert pick([100, 10, 10, 10], 4.0, 4) is None           # run too short
    assert pick([40, 10, 10, 10, 10], 4.0, 4) == (1, 5)      # 40 !< 4*10
    assert pick([39, 12, 10, 11, 10], 4.0, 4) == (0, 5)      # within band
    assert pick([], 4.0, 4) is None
    assert pick([0, 0, 0, 0], 4.0, 4) == (0, 4)              # empties merge
    # min_run clamps to 2: a single-segment "merge" would never make
    # progress and would spin the compact-until-quiescent loop
    assert pick([5], 4.0, 1) is None
    assert pick([5, 5], 4.0, 1) == (0, 2)
    p = compaction.TieredPolicy(size_ratio=4.0, min_run=2)
    assert p.pick([8, 9]) == (0, 2)


def test_oversized_doc_direct_seal_and_empty_docs():
    """A doc larger than the delta's posting capacity seals directly as
    its own segment; zero-term docs stay live (norm 1e-12) either way."""
    vocab = 64
    hashes = (np.arange(1, vocab + 1, dtype=np.uint32) * 40503
              ).astype(np.uint32)
    big = np.arange(vocab, dtype=np.int64)
    tc = TokenizedCorpus(
        doc_term_ids=[np.asarray([0, 1], np.int64), big,
                      np.zeros(0, np.int64)],
        doc_counts=[np.asarray([1, 1], np.int64),
                    np.ones(vocab, np.int64), np.zeros(0, np.int64)],
        term_hashes=hashes, num_docs=3)
    si = SegmentedIndex(term_hashes=hashes, delta_doc_capacity=8,
                        delta_posting_capacity=16,
                        policy=compaction.TieredPolicy(min_run=100))
    si.add_batch(tc)
    assert si.num_docs == 3 and si.live_doc_count == 3
    assert si.num_segments >= 1      # the big doc forced a direct seal
    qh = np.zeros((1, 2), np.uint32)
    qh[0, 0] = hashes[5]             # only the big doc contains term 5
    ids = np.asarray(si.topk(qh, k=2).doc_ids)
    assert ids[0, 0] == 1
    _assert_live_parity(si, qh, k=2)


def test_to_host_roundtrip_matches_bulk():
    tc = corpus.generate(corpus.CorpusSpec(num_docs=150, vocab=200,
                                           avg_distinct=12, seed=6))
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=64,
                        delta_posting_capacity=4096)
    si.add_batch(_slices(tc, [0, 150])[0])
    host = si.to_host()
    ref = build.bulk_build(tc)
    np.testing.assert_array_equal(host.df, ref.df)
    np.testing.assert_array_equal(host.doc_ids, ref.doc_ids)
    np.testing.assert_array_equal(host.offsets, ref.offsets)
    np.testing.assert_allclose(host.norm, ref.norm, rtol=1e-6)


def test_adaptive_budget_converges_to_zero_overflow():
    """ROADMAP follow-up: per-n_terms budgets derived from the overflow
    counter + a rolling sample — an overflowing workload converges to
    zero overflow warnings and stays there."""
    tc = corpus.generate(corpus.CorpusSpec(num_docs=400, vocab=400,
                                           avg_distinct=25, seed=2))
    host = build.bulk_build(tc)
    ix = layouts.build_blocked(host)
    cap = host.max_posting_len
    budget = query.AdaptiveRoutingBudget(initial=8)
    scorer = query.make_adaptive_scorer(ix, k=10, cap=cap, budget=budget)
    oracle = query.make_scorer(ix, k=10, cap=cap)
    stream = [corpus.sample_query_terms(host.df, host.term_hashes, 4, 4,
                                        num_docs=400, seed=s)
              for s in range(10)]
    overflows = []
    for qh in stream:
        _, stats = scorer(jnp.asarray(qh))
        overflows.append(int(stats["pair_overflow"]))
    assert overflows[0] > 0                       # deliberately undersized
    assert all(o == 0 for o in overflows[2:]), overflows
    # converged results match the default-budget oracle exactly
    r, _ = scorer(jnp.asarray(stream[-1]))
    ref = oracle(jnp.asarray(stream[-1]))
    np.testing.assert_array_equal(np.asarray(r.doc_ids),
                                  np.asarray(ref.doc_ids))
    # budgets stay quantized (bounded compile set)
    for v in budget._budgets.values():
        assert v & (v - 1) == 0


@pytest.mark.slow
def test_long_randomized_churn_sweep():
    """Long schedule: interleaved adds/deletes/compactions with parity,
    delete exclusion, and cache stability checked throughout."""
    rng = np.random.default_rng(42)
    tc = corpus.generate(corpus.CorpusSpec(num_docs=1200, vocab=400,
                                           avg_distinct=16, seed=21))
    bounds = np.linspace(0, 1200, 17).astype(int)
    batches = _slices(tc, bounds)
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=40,
                        delta_posting_capacity=2048,
                        policy=compaction.TieredPolicy(size_ratio=4.0,
                                                       min_run=4))
    qh = corpus.sample_query_terms(build.bulk_build(tc).df,
                                   tc.term_hashes, 3, 3,
                                   num_docs=tc.num_docs, seed=9)
    deleted = set()
    snap = None
    for step, b in enumerate(batches):
        si.add_batch(b)
        live = np.flatnonzero(si.live_mask())
        kill = rng.choice(live, size=min(11, len(live)), replace=False)
        si.delete(kill)
        deleted.update(int(x) for x in kill)
        _assert_live_parity(si, qh, k=12)
        ids = np.asarray(si.topk(qh, k=12).doc_ids)
        assert not np.isin(ids[ids >= 0], list(deleted)).any()
        if step == 8:
            snap = li.scorer_cache_sizes()
    # a randomized tiered cascade may mint a handful of NEW size classes
    # late in the sweep (compile set is log-bounded, not frozen); the
    # strict zero-growth contract for WARM classes is pinned by
    # test_churn_no_new_compilations_after_warmup
    growth = (sum(li.scorer_cache_sizes().values()) -
              sum(snap.values()))
    assert 0 <= growth <= 4, (snap, li.scorer_cache_sizes())
    assert si.stats.compactions >= 2


DISTRIBUTED_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.text import corpus
from repro.core import build, compaction
from repro.core.live_index import SegmentedIndex
from repro.distributed import retrieval

mesh = jax.make_mesh((4,), ("data",))
tc = corpus.generate(corpus.CorpusSpec(num_docs=500, vocab=400,
                                       avg_distinct=22, seed=9))
si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=64,
                    delta_posting_capacity=4096,
                    policy=compaction.TieredPolicy(min_run=100))
for a in range(0, 500, 100):
    si.add_batch(build.TokenizedCorpus(tc.doc_term_ids[a:a+100],
                                       tc.doc_counts[a:a+100],
                                       tc.term_hashes, 100))
deleted = [7, 123, 456]
si.delete(deleted)
si.seal()
assert si.num_segments >= 4
stacks = retrieval.stack_segment_shards(si, 4)
scorer = retrieval.make_doc_sharded_segment_scorer(stacks, mesh, "data",
                                                   k=10)
qh = corpus.sample_query_terms(np.asarray(si._df), si.term_hashes, 3, 3,
                               num_docs=si.live_doc_count, seed=3)
for q in qh:
    vv, ids = scorer(jnp.asarray(q))
    ref = si.topk(q[None], k=10)
    # contiguous per-shard runs preserve ascending doc-id source order,
    # so the sharded merge reproduces the single-node ranking EXACTLY
    # (ties included), not just the same doc set
    np.testing.assert_array_equal(np.asarray(ids),
                                  np.asarray(ref.doc_ids)[0])
    np.testing.assert_allclose(np.asarray(vv),
                               np.asarray(ref.scores)[0], rtol=1e-5)
    assert not np.isin(np.asarray(ids), deleted).any()
print("LIVE_SHARDED_OK")

# sharding a PINNED VIEW: the stacks snapshot one epoch; later deletes
# on the live index do not leak into the sharded serving tier
view = si.view()
si.delete([11, 222])
stacks_v = retrieval.stack_segment_shards(view, 4)
scorer_v = retrieval.make_doc_sharded_segment_scorer(stacks_v, mesh,
                                                     "data", k=10)
for q in qh[:2]:
    vv, ids = scorer_v(jnp.asarray(q))
    ref = view.topk(q[None], k=10)
    np.testing.assert_array_equal(np.asarray(ids),
                                  np.asarray(ref.doc_ids)[0])
    np.testing.assert_allclose(np.asarray(vv),
                               np.asarray(ref.scores)[0], rtol=1e-5)
print("VIEW_SHARDED_OK")
"""


def test_doc_sharded_segment_stack_scorer():
    """Doc-sharded serving tier over per-shard segment stacks: agrees
    with the single-node live index, honours tombstones, in a 4-device
    subprocess (XLA_FLAGS must be set before jax initializes)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", DISTRIBUTED_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=500)
    assert "LIVE_SHARDED_OK" in out.stdout, out.stderr[-3000:]
    assert "VIEW_SHARDED_OK" in out.stdout, out.stderr[-3000:]


@pytest.mark.parametrize("layout", ["hor", "packed"])
def test_engines_bit_identical_on_multi_term_batches(layout):
    """Scores, not only ids: the fused engines and the jnp oracle add a
    doc's per-term contributions in the same (ascending term id) order,
    so 3- and 4-term queries agree to the bit across a sealed segment
    and the delta."""
    tc = corpus.generate(corpus.CorpusSpec(num_docs=700, vocab=300,
                                           avg_distinct=30, seed=21))
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=64,
                        delta_posting_capacity=64 * 64)
    first, rest = _slices(tc, [0, 660, 700])
    si.add_batch(first)
    si.seal(layout=layout)
    si.add_batch(rest)
    qb = np.zeros((8, 8), np.uint32)
    for i, n_terms in enumerate((3, 4)):
        qb[4 * i:4 * i + 4, :n_terms] = corpus.sample_query_terms(
            np.asarray(si._df), si.term_hashes, 4, n_terms,
            num_docs=si.live_doc_count, seed=30 + i)
    want = si.topk(qb, k=10, engine="jnp")
    got = si.topk(qb, k=10)
    np.testing.assert_array_equal(np.asarray(got.doc_ids),
                                  np.asarray(want.doc_ids))
    np.testing.assert_array_equal(
        np.asarray(got.scores).view(np.uint32),
        np.asarray(want.scores).view(np.uint32))


def test_query_weights_do_not_depend_on_batch_shape():
    """Every server takes its query weights from one host function, so a
    row gets the same bits alone or inside any batch."""
    rng = np.random.default_rng(3)
    df = rng.integers(0, 5000, size=(8, 8)).astype(np.int32)
    df[:, 5:] = 0
    w, qn = li.query_weights(df, 12345)
    for i in range(8):
        w1, qn1 = li.query_weights(df[i], 12345)
        np.testing.assert_array_equal(w1.view(np.uint32),
                                      w[i].view(np.uint32))
        assert qn1.view(np.uint32) == qn[i].view(np.uint32)
