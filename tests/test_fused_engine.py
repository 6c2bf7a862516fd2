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


def _np_owner(offs, max_pairs):
    """The binary-search expansion that ``span_owners`` replaces."""
    s = len(offs) - 1
    p = np.arange(max_pairs)
    return np.clip(np.searchsorted(offs, p, side="right") - 1, 0,
                   max(s - 1, 0))


def _np_batched_pairs(block, valid, q, w, tfirst, tcount, n_tiles,
                      num_queries, max_pairs, cap):
    """numpy reference of ``build_batched_pairs``, step for step."""
    s = len(block)
    key = np.where(valid, block, 2**30)
    order = np.argsort(key, kind="stable")
    k_s = key[order]
    valid_s = k_s < 2**30
    uniq = valid_s & np.concatenate([[True], k_s[1:] != k_s[:-1]])
    uid = np.cumsum(uniq) - 1
    total_u = int(uniq.sum())
    ublock = np.zeros(s, np.int32)
    ublock[uid[uniq]] = k_s[uniq]
    qw = np.zeros((s, num_queries), np.float32)
    ucap = np.zeros(s, np.int32)
    for i in np.flatnonzero(valid_s):
        qw[uid[i], q[order[i]]] += w[order[i]]
        ucap[uid[i]] = max(ucap[uid[i]], cap[order[i]])
    cnt = np.where(np.arange(s) < total_u, tcount[ublock], 0)
    offs = np.concatenate([[0], np.cumsum(cnt)]).astype(np.int32)
    total = int(offs[-1])
    p = np.arange(max_pairs)
    owner = _np_owner(offs, max_pairs)
    real = p < total
    pair_block = np.where(real, ublock[owner], 0).astype(np.int32)
    pair_tile = np.where(real, tfirst[ublock][owner] + (p - offs[owner]),
                         n_tiles).astype(np.int32)
    t_order = np.argsort(pair_tile, kind="stable")
    pair_qw = qw[owner[t_order]] * real[t_order][:, None]
    return (pair_block[t_order], pair_tile[t_order], pair_qw,
            ucap[owner[t_order]], max(total - max_pairs, 0))


def _np_pairs(blocks, valid, w, tfirst, tcount, n_tiles, max_pairs):
    """numpy reference of ``posting_score.build_pairs``."""
    safe = np.maximum(blocks, 0)
    span = np.where(valid, tcount[safe], 0)
    offs = np.concatenate([[0], np.cumsum(span)]).astype(np.int32)
    total = int(offs[-1])
    p = np.arange(max_pairs)
    owner = _np_owner(offs, max_pairs)
    real = p < total
    pair_block = np.where(real, safe[owner], 0).astype(np.int32)
    pair_tile = np.where(real, tfirst[safe][owner] + (p - offs[owner]),
                         n_tiles).astype(np.int32)
    pair_w = np.where(real, w[owner], 0.0).astype(np.float32)
    order = np.argsort(pair_tile, kind="stable")
    return (pair_block[order], pair_tile[order], pair_w[order],
            max(total - max_pairs, 0))


def _routing_case(name):
    """Candidates for one routing case: (block, valid, q, w, tile_first,
    tile_count, n_tiles, num_queries, max_pairs)."""
    rng = np.random.default_rng(11)
    nb, n_tiles, nq = 64, 40, 4
    tfirst = rng.integers(0, n_tiles - 3, nb).astype(np.int32)
    tcount = rng.integers(1, 4, nb).astype(np.int32)
    s, max_pairs = 48, 96
    block = rng.integers(0, nb, s).astype(np.int32)
    valid = rng.random(s) < 0.6
    if name == "duplicate_blocks":
        block[s // 2:] = block[:s // 2]     # each block in two queries
    elif name == "zero_tile_spans":
        tcount[::3] = 0
    elif name == "over_budget":
        valid[:] = True
        max_pairs = 20
    elif name == "no_valid_candidate":
        valid[:] = False
    elif name == "one_candidate":
        s, max_pairs = 1, 8
        block, valid = block[:1], np.ones(1, bool)
    elif name == "segment0_ratio":
        # paper-1m's first segment: a budget 1.25x the candidates, ~1/10
        # of the candidates valid
        s, max_pairs = 1376, 1720
        block = rng.integers(0, nb, s).astype(np.int32)
        valid = rng.random(s) < 0.1
    q = (np.arange(s) * nq // s).astype(np.int32)
    # one candidate per (block, query): distinct terms own distinct blocks
    _, first = np.unique(np.stack([block, q]), axis=1, return_index=True)
    keep = np.zeros(s, bool)
    keep[first] = True
    valid &= keep
    w = (rng.integers(1, 64, s) / 16).astype(np.float32)
    return block, valid, q, w, tfirst, tcount, n_tiles, nq, max_pairs


ROUTING_CASES = ["duplicate_blocks", "zero_tile_spans", "over_budget",
                 "no_valid_candidate", "one_candidate", "segment0_ratio"]


@pytest.mark.parametrize("case", ROUTING_CASES)
def test_batched_pairs_match_binary_search_reference(case):
    """The scatter-and-prefix-sum expansion routes exactly as the binary
    search did: every output bit-equal, overflow included."""
    from repro.kernels.fused_decode_score import build_batched_pairs
    block, valid, q, w, tfirst, tcount, n_tiles, nq, mp = \
        _routing_case(case)
    cap = (block % 7 + 1).astype(np.int32)
    got = build_batched_pairs(
        jnp.asarray(block), jnp.asarray(valid), jnp.asarray(q),
        jnp.asarray(w), jnp.asarray(tfirst), jnp.asarray(tcount), n_tiles,
        nq, mp, jnp.asarray(cap))
    want = _np_batched_pairs(block, valid, q, w, tfirst, tcount, n_tiles,
                             nq, mp, cap)
    for g, e in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), e)
    if case == "over_budget":
        assert int(got[-1]) > 0


@pytest.mark.parametrize("case", ROUTING_CASES)
def test_single_query_pairs_match_binary_search_reference(case):
    from repro.kernels.posting_score import build_pairs
    block, valid, _, w, tfirst, tcount, n_tiles, _, mp = _routing_case(case)
    block = np.where(valid, block, -1).astype(np.int32)
    got = build_pairs(jnp.asarray(block), jnp.asarray(valid),
                      jnp.asarray(w), jnp.asarray(tfirst),
                      jnp.asarray(tcount), n_tiles, mp)
    want = _np_pairs(block, valid, w, tfirst, tcount, n_tiles, mp)
    for g, e in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), e)


@pytest.mark.parametrize("s, max_pairs", [(0, 16), (1, 1), (5, 3),
                                          (40, 200)])
def test_span_owners_edge_shapes(s, max_pairs):
    from repro.kernels.fused_decode_score import span_owners
    rng = np.random.default_rng(s)
    offs = np.concatenate([[0], np.cumsum(rng.integers(0, 4, s))])
    got = span_owners(jnp.asarray(offs, jnp.int32), max_pairs)
    np.testing.assert_array_equal(np.asarray(got),
                                  _np_owner(offs, max_pairs))


def test_routing_lowers_without_a_loop():
    """At paper-1m's first segment (172,032 candidates, a 214,976-pair
    budget) routing lowers to straight-line ops: no ``while``, whose
    every trip would gather the whole pair budget."""
    import jax
    from repro.kernels.fused_decode_score import build_batched_pairs
    s, max_pairs, nb = 172_032, 214_976, 642_432
    i32 = jax.ShapeDtypeStruct((s,), jnp.int32)
    args = (i32, jax.ShapeDtypeStruct((s,), jnp.bool_), i32,
            jax.ShapeDtypeStruct((s,), jnp.float32),
            jax.ShapeDtypeStruct((nb,), jnp.int32),
            jax.ShapeDtypeStruct((nb,), jnp.int32), i32)
    text = jax.jit(
        lambda b, v, q, w, tf, tc, cap: build_batched_pairs(
            b, v, q, w, tf, tc, 672, 8, max_pairs, cap)
    ).lower(*args).as_text()
    assert "stablehlo.while" not in text
