"""Parity tests: fused batched decode-and-score engine vs the jnp oracle.

``make_scorer(engine="pallas")`` must return bit-identical top-k doc ids
to ``score_queries`` (the pure-jnp oracle) across the HOR and Packed
layouts — including deleted docs (norm == 0), absent terms, empty
queries, and k > hits.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build, layouts, query
from repro.core.layouts import DocTable
from repro.text import corpus


def _host(seed=7, docs=600, vocab=500, avg=25):
    tc = corpus.generate(corpus.CorpusSpec(num_docs=docs, vocab=vocab,
                                           avg_distinct=avg, seed=seed))
    return build.bulk_build(tc)


def _absent_hash(host):
    """A nonzero u32 hash guaranteed not to be in the vocabulary."""
    taken = set(int(h) for h in host.term_hashes)
    h = 12345
    while h in taken or h == 0:
        h += 1
    return np.uint32(h)


BUILDERS = {"hor": layouts.build_blocked, "packed": layouts.build_packed_csr}


def _assert_parity(ix, qh, k, cap, **scorer_kw):
    oracle = query.make_scorer(ix, k=k, cap=cap)(qh)
    fused = query.make_scorer(ix, k=k, cap=cap, engine="pallas",
                              **scorer_kw)(qh)
    np.testing.assert_array_equal(np.asarray(fused.doc_ids),
                                  np.asarray(oracle.doc_ids))
    np.testing.assert_allclose(np.asarray(fused.scores),
                               np.asarray(oracle.scores),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("layout", ["hor", "packed"])
def test_fused_matches_oracle_batched(layout):
    host = _host()
    ix = BUILDERS[layout](host)
    cap = max(host.max_posting_len, 1)
    qh = corpus.sample_query_terms(host.df, host.term_hashes, 8, 4,
                                   num_docs=host.num_docs, seed=3)
    _assert_parity(ix, jnp.asarray(qh), k=10, cap=cap)


@pytest.mark.parametrize("layout", ["hor", "packed"])
@pytest.mark.slow
def test_fused_shared_terms_across_batch(layout):
    """Queries sharing terms exercise the cross-query pair dedup."""
    host = _host()
    ix = BUILDERS[layout](host)
    cap = max(host.max_posting_len, 1)
    q = corpus.sample_query_terms(host.df, host.term_hashes, 2, 4,
                                  num_docs=host.num_docs, seed=5)
    qh = np.stack([q[0], q[0], q[1], q[0]])       # heavy term sharing
    qh[1, 2:] = q[1][2:]                          # partial overlap too
    _assert_parity(ix, jnp.asarray(qh), k=10, cap=cap)


@pytest.mark.parametrize("layout", ["hor", "packed"])
@pytest.mark.slow
def test_fused_absent_and_empty_terms(layout):
    host = _host()
    ix = BUILDERS[layout](host)
    cap = max(host.max_posting_len, 1)
    q = corpus.sample_query_terms(host.df, host.term_hashes, 1, 4,
                                  num_docs=host.num_docs, seed=1)[0]
    absent = _absent_hash(host)
    qh = np.zeros((3, 4), np.uint32)
    qh[0] = q
    qh[0, 1] = absent                 # absent term mixed into a real query
    qh[1, 0] = absent                 # only-absent-term query
    # qh[2] stays all zeros           # fully empty query
    _assert_parity(ix, jnp.asarray(qh), k=5, cap=cap)
    fused = query.make_scorer(ix, k=5, cap=cap, engine="pallas")(
        jnp.asarray(qh))
    assert (np.asarray(fused.doc_ids)[1:] == -1).all()


@pytest.mark.parametrize("layout", ["hor", "packed"])
@pytest.mark.slow
def test_fused_deleted_docs(layout):
    """Docs with norm == 0 are deleted: never returned by either engine."""
    host = _host()
    ix = BUILDERS[layout](host)
    cap = max(host.max_posting_len, 1)
    norm = np.asarray(ix.docs.norm).copy()
    deleted = np.arange(0, host.num_docs, 3)
    norm[deleted] = 0.0
    ix = dataclasses.replace(
        ix, docs=DocTable(norm=jnp.asarray(norm), rank=ix.docs.rank))
    qh = corpus.sample_query_terms(host.df, host.term_hashes, 4, 3,
                                   num_docs=host.num_docs, seed=2)
    _assert_parity(ix, jnp.asarray(qh), k=10, cap=cap)
    fused = query.make_scorer(ix, k=10, cap=cap, engine="pallas")(
        jnp.asarray(qh))
    ids = np.asarray(fused.doc_ids)
    assert not np.isin(ids[ids >= 0], deleted).any()


@pytest.mark.parametrize("layout", ["hor", "packed"])
def test_fused_k_exceeds_hits(layout):
    """k larger than the number of matching docs pads with -1, like the
    oracle."""
    host = _host(docs=120, vocab=400, avg=8)
    ix = BUILDERS[layout](host)
    cap = max(host.max_posting_len, 1)
    # rare term: few hits, k much larger
    rare = int(np.argmin(np.where(host.df > 0, host.df, 10**9)))
    qh = np.zeros((1, 4), np.uint32)
    qh[0, 0] = host.term_hashes[rare]
    k = host.num_docs  # way past any df
    _assert_parity(ix, jnp.asarray(qh), k=k, cap=cap)
    fused = query.make_scorer(ix, k=k, cap=cap, engine="pallas")(
        jnp.asarray(qh))
    assert (np.asarray(fused.doc_ids)[0] == -1).sum() >= k - int(
        host.df[rare])


@pytest.mark.parametrize("layout", ["hor", "packed"])
@pytest.mark.slow
def test_fused_rank_blend(layout):
    host = _host()
    ix = BUILDERS[layout](host)
    cap = max(host.max_posting_len, 1)
    qh = corpus.sample_query_terms(host.df, host.term_hashes, 4, 3,
                                   num_docs=host.num_docs, seed=8)
    oracle = query.make_scorer(ix, k=10, cap=cap, rank_blend=0.5)(
        jnp.asarray(qh))
    fused = query.make_scorer(ix, k=10, cap=cap, rank_blend=0.5,
                              engine="pallas")(jnp.asarray(qh))
    np.testing.assert_array_equal(np.asarray(fused.doc_ids),
                                  np.asarray(oracle.doc_ids))


@pytest.mark.parametrize("layout", ["hor", "packed"])
def test_fused_overflow_is_detected(layout):
    """An undersized routing budget is SURFACED (stats counter), not a
    silent posting drop."""
    host = _host()
    ix = BUILDERS[layout](host)
    cap = max(host.max_posting_len, 1)
    qh = corpus.sample_query_terms(host.df, host.term_hashes, 4, 4,
                                   num_docs=host.num_docs, seed=4)
    _, stats = query.make_scorer(ix, k=10, cap=cap, engine="pallas",
                                 max_pairs=2, return_stats=True)(
        jnp.asarray(qh))
    assert int(stats["pair_overflow"]) > 0


@pytest.mark.parametrize("layout", ["hor", "packed"])
@pytest.mark.slow
def test_fused_default_budget_never_overflows(layout):
    """The build-time route_pairs_max budget is an exact upper bound at
    the default tile: overflow must be 0 without tuning."""
    host = _host()
    ix = BUILDERS[layout](host)
    cap = max(host.max_posting_len, 1)
    qh = corpus.sample_query_terms(host.df, host.term_hashes, 8, 4,
                                   num_docs=host.num_docs, seed=6)
    _, stats = query.make_scorer(ix, k=10, cap=cap, engine="pallas",
                                 return_stats=True)(jnp.asarray(qh))
    assert int(stats["pair_overflow"]) == 0


@pytest.mark.parametrize("layout", ["hor", "packed"])
@pytest.mark.parametrize("backend", ["pallas", "xla"])
@pytest.mark.slow
def test_fused_mid_block_cap_matches_oracle(layout, backend):
    """A posting cap that cuts MID-BLOCK (not a multiple of the 128-lane
    block) must truncate exactly like the oracle's gather."""
    host = _host()
    ix = BUILDERS[layout](host)
    qh = corpus.sample_query_terms(host.df, host.term_hashes, 4, 3,
                                   num_docs=host.num_docs, seed=11)
    for cap in (130, 257, 100):
        _assert_parity(ix, jnp.asarray(qh), k=10, cap=cap, backend=backend)


@pytest.mark.parametrize("layout", ["hor", "packed"])
@pytest.mark.slow
def test_fused_xla_backend_matches_oracle(layout):
    """The plain-HLO lowering of the fused engine (same block dedup,
    wide-row scatter) ranks identically too."""
    host = _host()
    ix = BUILDERS[layout](host)
    cap = max(host.max_posting_len, 1)
    qh = corpus.sample_query_terms(host.df, host.term_hashes, 8, 3,
                                   num_docs=host.num_docs, seed=9)
    _assert_parity(ix, jnp.asarray(qh), k=10, cap=cap, backend="xla")


@pytest.mark.parametrize("layout", ["hor", "packed"])
@pytest.mark.parametrize("backend", ["pallas", "xla"])
@pytest.mark.slow
def test_fused_duplicate_terms_match_oracle(layout, backend):
    """Regression: a term hash repeated across slots of one query must
    be scored ONCE by every engine (the gather used to double-count its
    tf·idf weight and inflate the query norm)."""
    host = _host()
    ix = BUILDERS[layout](host)
    cap = max(host.max_posting_len, 1)
    q = corpus.sample_query_terms(host.df, host.term_hashes, 2, 4,
                                  num_docs=host.num_docs, seed=13)
    qh = np.stack([q[0], q[0], q[1]])
    qh[0, 1] = qh[0, 0]               # duplicate inside one query
    qh[1, 3] = qh[1, 2]
    qh[2, 1:] = qh[2, 0]              # one term repeated in every slot
    _assert_parity(ix, jnp.asarray(qh), k=10, cap=cap, backend=backend)
    # duplicated slots change nothing vs the deduplicated query
    dedup = np.zeros_like(qh[2:3])
    dedup[0, 0] = qh[2, 0]
    a = query.make_scorer(ix, k=10, cap=cap, engine="pallas")(
        jnp.asarray(qh[2:3]))
    b = query.make_scorer(ix, k=10, cap=cap, engine="pallas")(
        jnp.asarray(dedup))
    np.testing.assert_array_equal(np.asarray(a.doc_ids),
                                  np.asarray(b.doc_ids))


def _tied_host(num_docs=1200):
    """Synthetic postings engineered for exact score TIES: term A covers
    every doc at tf=1, term B the upper half at tf=2; all norms equal.
    Querying A alone makes every doc's final score identical."""
    from repro.core.layouts import PostingsHost
    half = num_docs // 2
    term_hashes = np.array([111, 222], np.uint64).astype(np.uint32)
    doc_a = np.arange(num_docs, dtype=np.int32)
    doc_b = np.arange(half, num_docs, dtype=np.int32)
    return PostingsHost(
        term_hashes=term_hashes,
        df=np.array([num_docs, num_docs - half], np.int32),
        offsets=np.array([0, num_docs, num_docs + (num_docs - half)],
                         np.int64),
        doc_ids=np.concatenate([doc_a, doc_b]),
        tfs=np.concatenate([np.ones(num_docs, np.float32),
                            np.full(num_docs - half, 2.0, np.float32)]),
        num_docs=num_docs,
        norm=np.ones(num_docs, np.float32),
        rank=np.zeros(num_docs, np.float32))


@pytest.mark.parametrize("layout", ["hor", "packed"])
@pytest.mark.slow
def test_fused_tie_breaking_matches_oracle(layout):
    """Hundreds of exactly-tied docs spanning several 512-doc tiles: the
    per-tile candidate lists must merge with the oracle's lowest-doc-id
    tie order, bit-identically."""
    host = _tied_host()
    ix = BUILDERS[layout](host)
    cap = host.num_docs
    qh = np.zeros((2, 4), np.uint32)
    qh[0, 0] = 111                    # every doc tied
    qh[1, 0] = 111
    qh[1, 1] = 222                    # upper half breaks away, lower ties
    _assert_parity(ix, jnp.asarray(qh), k=25, cap=cap)
    fused = query.make_scorer(ix, k=25, cap=cap, engine="pallas")(
        jnp.asarray(qh))
    # all-tied query: ties resolve to the lowest doc ids, in order
    np.testing.assert_array_equal(np.asarray(fused.doc_ids)[0],
                                  np.arange(25))


@pytest.mark.parametrize("layout", ["hor", "packed"])
@pytest.mark.slow
def test_fused_deleted_docs_winning_tiles(layout):
    """Delete exactly the docs that WON the query (norm = 0): the
    tile-local top-k must skip them in-kernel, not return them and lose
    the real winners."""
    host = _host()
    ix = BUILDERS[layout](host)
    cap = max(host.max_posting_len, 1)
    qh = corpus.sample_query_terms(host.df, host.term_hashes, 2, 3,
                                   num_docs=host.num_docs, seed=21)
    winners = np.asarray(query.make_scorer(ix, k=10, cap=cap)(
        jnp.asarray(qh)).doc_ids)
    deleted = np.unique(winners[winners >= 0])
    norm = np.asarray(ix.docs.norm).copy()
    norm[deleted] = 0.0
    ix = dataclasses.replace(
        ix, docs=DocTable(norm=jnp.asarray(norm), rank=ix.docs.rank))
    _assert_parity(ix, jnp.asarray(qh), k=10, cap=cap)
    fused = query.make_scorer(ix, k=10, cap=cap, engine="pallas")(
        jnp.asarray(qh))
    ids = np.asarray(fused.doc_ids)
    assert not np.isin(ids[ids >= 0], deleted).any()
    assert (ids >= 0).any()           # the runners-up surface instead


@pytest.mark.parametrize("layout", ["hor", "packed"])
@pytest.mark.slow
def test_fused_all_tiles_empty_query(layout):
    """A query whose every tile is empty (no terms / absent terms) in a
    batch with real queries returns all -1 via the candidate path."""
    host = _host()
    ix = BUILDERS[layout](host)
    cap = max(host.max_posting_len, 1)
    q = corpus.sample_query_terms(host.df, host.term_hashes, 1, 4,
                                  num_docs=host.num_docs, seed=17)[0]
    qh = np.zeros((3, 4), np.uint32)
    qh[0] = q                         # real query keeps tiles visited
    qh[2, 0] = _absent_hash(host)     # absent-only query
    _assert_parity(ix, jnp.asarray(qh), k=7, cap=cap)
    fused = query.make_scorer(ix, k=7, cap=cap, engine="pallas")(
        jnp.asarray(qh))
    assert (np.asarray(fused.doc_ids)[1:] == -1).all()
    assert (np.asarray(fused.scores)[1:] == 0.0).all()


@pytest.mark.parametrize("layout", ["hor", "packed"])
@pytest.mark.slow
def test_fused_kernel_candidates_match_jnp_extraction(layout):
    """The in-kernel per-tile reduction must equal the pure-jnp
    ``extract_tile_candidates`` mirror applied to the SAME dense
    accumulator (identical pair order -> bit-identical scores)."""
    from repro.kernels import ops
    from repro.kernels.fused_decode_score import (
        TILE, default_k_tile, extract_tile_candidates)
    host = _host()
    ix = BUILDERS[layout](host)
    cap = max(host.max_posting_len, 1)
    k = 10
    qh = corpus.sample_query_terms(host.df, host.term_hashes, 4, 3,
                                   num_docs=host.num_docs, seed=19)
    present = jnp.asarray(qh) != 0
    tids = jnp.where(present, ix.lookup_terms(jnp.asarray(qh)), -1)
    idf_t = query.idf(ix.term_df(tids), host.num_docs)
    qnorm = jnp.sqrt(jnp.maximum(jnp.sum(idf_t * idf_t, axis=1), 1e-12))
    dense, _ = ops.fused_batched_scores(ix, tids, idf_t, cap)
    final = query.final_scores(dense, ix.docs.norm, ix.docs.rank, qnorm,
                               0.0)
    want_v, want_i = extract_tile_candidates(final, TILE,
                                             default_k_tile(k))
    got_v, got_i, _ = ops.fused_batched_topk(ix, tids, idf_t, cap, k)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))


def test_merge_topk_candidates_pads_short_lists():
    """k beyond the candidate count pads with -inf / -1 instead of
    crashing (jax.lax.top_k requires k <= n)."""
    from repro.distributed.topk import merge_topk_candidates
    v = jnp.asarray([[3.0, 1.0], [2.0, -jnp.inf]])
    i = jnp.asarray([[30, 10], [20, -1]], dtype=jnp.int32)
    mv, mi = merge_topk_candidates(v, i, k=4)
    np.testing.assert_array_equal(np.asarray(mi),
                                  [[30, 10, -1, -1], [20, -1, -1, -1]])
    assert np.asarray(mv)[0, 2] == -np.inf


def test_make_scorer_rejects_unknown_engine():
    host = _host(docs=60, vocab=80, avg=5)
    ix = layouts.build_blocked(host)
    with pytest.raises(ValueError):
        query.make_scorer(ix, k=5, cap=8, engine="cuda")


def test_make_scorer_rejects_unblocked_index_for_pallas():
    host = _host(docs=60, vocab=80, avg=5)
    with pytest.raises(TypeError, match="BlockedIndex or PackedCsrIndex"):
        query.make_scorer(layouts.build_csr(host), k=5, cap=8,
                          engine="pallas")


def test_term_pairs_bound_holds_for_every_term():
    """The per-batch routing budget rests on this bound: a term's
    (block, tile) pairs never exceed n_tiles + blocks - 1, at the route
    tile and at a narrower one."""
    from repro.kernels import ops
    host = _host()
    ix = layouts.build_packed_csr(host)
    offs = np.asarray(ix.block_offsets)
    for tile in (ix.route_tile, 128):
        _, tcount, n_tiles = ops.routing_spans(ix, tile)
        tcount = np.asarray(tcount)
        for t in range(len(offs) - 1):
            nb = int(offs[t + 1] - offs[t])
            if nb:
                pairs = int(tcount[offs[t]:offs[t + 1]].sum())
                assert pairs <= ops.term_pairs_bound(1, nb, n_tiles), t
