"""Query evaluation vs a brute-force numpy oracle, and BM25 on the
served path vs its plain reference."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compaction, layouts, query
from repro.core.build import TokenizedCorpus
from repro.core.live_index import SegmentedIndex
from repro.core.ranking import TFIDF_COSINE, Ranking, bm25
from repro.kernels.ref import ref_bm25_topk
from repro.serve import QueryServer, ServerConfig
from repro.serve.snapshot import restore_segmented, serialize_segmented
from repro.text import corpus


def brute_force_scores(host, hashes):
    """Direct tf-idf cosine from the canonical postings."""
    h2t = {int(h): i for i, h in enumerate(host.term_hashes)}
    scores = np.zeros(host.num_docs)
    idf = {}
    w2 = 0.0
    for h in hashes:
        t = h2t.get(int(h))
        if t is None or h == 0:
            continue
        idf_t = np.log1p(host.num_docs / max(host.df[t], 1))
        idf[t] = idf_t
        w2 += idf_t ** 2
        s, e = host.offsets[t], host.offsets[t + 1]
        scores[host.doc_ids[s:e]] += host.tfs[s:e] * idf_t
    qnorm = np.sqrt(max(w2, 1e-12))
    return scores / (np.maximum(host.norm, 1e-12) * qnorm)


def test_matches_brute_force(small_host, query_hashes):
    ix = layouts.build_csr(small_host)
    cap = small_host.max_posting_len
    for q in query_hashes[:3]:
        r = query.score_query(ix, jnp.asarray(q), k=10, cap=cap)
        ref = brute_force_scores(small_host, q)
        order = np.argsort(ref)[::-1][:10]
        np.testing.assert_allclose(np.asarray(r.scores), ref[order],
                                   rtol=1e-5)


def test_conjunctive_is_subset_of_disjunctive(small_host, query_hashes):
    ix = layouts.build_csr(small_host)
    cap = small_host.max_posting_len
    q = jnp.asarray(query_hashes[0][:2])
    conj, _ = query.conjunctive_filter(ix, q, k=50, cap=cap)
    h2t = {int(h): i for i, h in enumerate(small_host.term_hashes)}
    for d in np.asarray(conj.doc_ids):
        if d < 0:
            continue
        for h in np.asarray(q):
            t = h2t[int(h)]
            s, e = small_host.offsets[t], small_host.offsets[t + 1]
            assert d in small_host.doc_ids[s:e]


def test_conjunctive_counts_are_exact_ints(small_host, query_hashes):
    """Regression: AND-membership counting must use an integer
    accumulator — float32 loses integer exactness past 2**24, which
    silently mis-filters long posting lists."""
    ix = layouts.build_csr(small_host)
    cap = small_host.max_posting_len
    q = jnp.asarray(query_hashes[0][:2])
    counts_dtype = query.accumulate_counts(
        jnp.zeros((1, 4), jnp.int32), jnp.ones((1, 4), bool), 8).dtype
    assert counts_dtype == jnp.int32
    # AND result equals the numpy ground truth doc set
    conj, _ = query.conjunctive_filter(ix, q, k=small_host.num_docs, cap=cap)
    got = set(int(d) for d in np.asarray(conj.doc_ids) if d >= 0)
    h2t = {int(h): i for i, h in enumerate(small_host.term_hashes)}
    want = None
    for h in np.asarray(q):
        t = h2t[int(h)]
        s, e = small_host.offsets[t], small_host.offsets[t + 1]
        docs = set(small_host.doc_ids[s:e].tolist())
        want = docs if want is None else want & docs
    assert got == want


def test_absent_and_empty_terms(small_host):
    ix = layouts.build_csr(small_host)
    cap = small_host.max_posting_len
    q = jnp.asarray([0, 0, 0, 0], dtype=jnp.uint32)      # empty query
    r = query.score_query(ix, q, k=5, cap=cap)
    assert (np.asarray(r.doc_ids) == -1).all()


def test_duplicate_terms_score_once(small_host, query_hashes):
    """Regression: the same term hash in two query slots must contribute
    ONCE — the gather phase reads one posting list per slot, so without
    dedup tf·idf weight is double-counted and the query norm inflates."""
    ix = layouts.build_csr(small_host)
    cap = small_host.max_posting_len
    h = query_hashes[0][0]
    single = jnp.asarray(np.array([h, 0, 0, 0], np.uint32))
    doubled = jnp.asarray(np.array([h, h, 0, h], np.uint32))
    rs = query.score_query(ix, single, k=10, cap=cap)
    rd = query.score_query(ix, doubled, k=10, cap=cap)
    np.testing.assert_array_equal(np.asarray(rs.doc_ids),
                                  np.asarray(rd.doc_ids))
    np.testing.assert_allclose(np.asarray(rs.scores), np.asarray(rd.scores))


def test_dedup_query_hashes_keeps_first_only():
    qh = jnp.asarray(np.array([[7, 7, 0, 7], [1, 2, 1, 2]], np.uint32))
    got = np.asarray(query.dedup_query_hashes(qh))
    np.testing.assert_array_equal(got, [[7, 0, 0, 0], [1, 2, 0, 0]])


def test_conjunctive_duplicate_terms_keep_and_semantics(small_host,
                                                        query_hashes):
    """A duplicated AND term must not change the result set (it used to
    inflate both the membership counts and the needed threshold, and
    double-count the score weights)."""
    ix = layouts.build_csr(small_host)
    cap = small_host.max_posting_len
    q2 = np.asarray(query_hashes[0][:2])
    plain, _ = query.conjunctive_filter(ix, jnp.asarray(q2), k=50, cap=cap)
    dup = np.array([q2[0], q2[1], q2[0], q2[1]], np.uint32)
    doubled, _ = query.conjunctive_filter(ix, jnp.asarray(dup), k=50,
                                          cap=cap)
    np.testing.assert_array_equal(np.asarray(plain.doc_ids),
                                  np.asarray(doubled.doc_ids))
    np.testing.assert_allclose(np.asarray(plain.scores),
                               np.asarray(doubled.scores))


def test_conjunctive_cap_truncation_is_surfaced(small_host, query_hashes):
    """A cap that truncates a posting list can undercount membership and
    silently drop true AND matches — the filter must SURFACE it."""
    ix = layouts.build_csr(small_host)
    cap = small_host.max_posting_len
    q = jnp.asarray(query_hashes[0][:2])
    _, stats = query.conjunctive_filter(ix, q, k=10, cap=cap)
    assert int(stats["truncated_terms"]) == 0      # full cap: exact
    h2t = {int(h): i for i, h in enumerate(small_host.term_hashes)}
    min_df = min(int(small_host.df[h2t[int(h)]]) for h in np.asarray(q))
    _, stats = query.conjunctive_filter(ix, q, k=10, cap=min_df - 1)
    assert int(stats["truncated_terms"]) > 0       # truncated: flagged


# ---------------------------------------------------------------------------
# BM25 on the served path (core/ranking.py) against the plain reference
# ---------------------------------------------------------------------------

K1, B = 0.9, 0.4


def _part(tc, a, b):
    return TokenizedCorpus(tc.doc_term_ids[a:b], tc.doc_counts[a:b],
                           tc.term_hashes, b - a)


def _live_index(seed, ranking):
    """Sealed segments of both layouts and a delta: 100 docs sealed
    hor, 80 sealed packed, 50 left in the delta; 70 more to ingest."""
    tc = corpus.generate(corpus.CorpusSpec(num_docs=300, vocab=250,
                                           avg_distinct=14, seed=seed))
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=128,
                        delta_posting_capacity=4096,
                        policy=compaction.TieredPolicy(size_ratio=4.0,
                                                       min_run=50),
                        ranking=ranking)
    si.add_batch(_part(tc, 0, 100))
    si.seal(layout="hor")
    si.add_batch(_part(tc, 100, 180))
    si.seal(layout="packed")
    si.add_batch(_part(tc, 180, 230))
    qh = corpus.sample_query_terms(np.bincount(
        np.concatenate(tc.doc_term_ids), minlength=250), tc.term_hashes,
        8, 4, num_docs=tc.num_docs, seed=seed + 1)
    return tc, si, np.asarray(qh, np.uint32)


def _served(si, qh):
    """Every row through QueryServer -> LiveView.topk, one batch."""
    server = QueryServer(si, ServerConfig(batch_size=len(qh),
                                          n_terms_budget=qh.shape[1], k=10,
                                          cache_capacity=0))
    tickets = [server.submit(row) for row in qh]
    assert server.pump() == len(qh)
    got = [t.result(timeout=0) for t in tickets]
    return (np.stack([r.doc_ids for r in got]),
            np.stack([r.scores for r in got]))


def _bm25_reference(si, qh):
    tc, live_ids = si.export_live_corpus()
    terms = [si.lookup_np(row[row != 0]) for row in qh]
    ids, scores = ref_bm25_topk(tc.doc_term_ids, tc.doc_counts, terms, 10,
                                K1, B)
    return np.where(ids >= 0, live_ids[np.maximum(ids, 0)], -1), scores


def _assert_bm25_served(si, qh):
    ids, scores = _served(si, qh)
    want_ids, want_scores = _bm25_reference(si, qh)
    np.testing.assert_array_equal(ids, want_ids)
    # float32 either side; the engines add a document's terms in their
    # own order (hash order in a sealed segment), the reference in term
    # id order, so a sum of up to 4 terms may round differently
    np.testing.assert_allclose(scores, want_scores, rtol=1e-6, atol=0)
    # the walk and the jnp engine agree to the bit
    jnp_r = si.view().topk(qh, 10, engine="jnp")
    np.testing.assert_array_equal(np.asarray(jnp_r.doc_ids), ids)
    np.testing.assert_array_equal(np.asarray(jnp_r.scores).view(np.uint32),
                                  scores.view(np.uint32))
    return scores


@pytest.mark.parametrize("seed", [4, 17])
def test_bm25_served_matches_reference_after_ingest_and_delete(seed):
    tc, si, qh = _live_index(seed, bm25(K1, B))
    assert sorted(s.layout for s in si.segments()) == ["hor", "packed"]
    assert si.view().delta_n_docs == 50
    _assert_bm25_served(si, qh)
    avgdl = si.view().avgdl
    si.add_batch(_part(tc, 230, 300))
    assert si.view().avgdl != avgdl
    _assert_bm25_served(si, qh)
    avgdl = si.view().avgdl
    # one doc of each sealed segment and two of the delta
    si.delete([7, 150, 231, 260])
    assert si.view().avgdl != avgdl
    ids, _ = _served(si, qh)
    assert not np.isin(ids, [7, 150, 231, 260]).any()
    _assert_bm25_served(si, qh)


def test_cosine_served_bit_identical_to_oracle():
    """The ranking seam leaves cosine's arithmetic as it was: the served
    walk equals the jnp oracle engine to the bit on the same seed."""
    tc, si, qh = _live_index(4, TFIDF_COSINE)
    si.delete([7, 150, 200])
    ids, scores = _served(si, qh)
    want = si.view().topk(qh, 10, engine="jnp")
    np.testing.assert_array_equal(ids, np.asarray(want.doc_ids))
    np.testing.assert_array_equal(
        scores.view(np.uint32), np.asarray(want.scores).view(np.uint32))
    assert (ids >= 0).any()


def test_doc_tables_hold_only_their_ranking_row():
    _, cos, _ = _live_index(4, TFIDF_COSINE)
    _, okapi, _ = _live_index(4, bm25(K1, B))
    for a, b in zip(cos.segments(), okapi.segments()):
        assert a.index.docs.length is None and a.index.docs.norm is not None
        assert b.index.docs.norm is None and b.index.docs.length is not None
        assert a.index.docs.nbytes() == b.index.docs.nbytes()


def test_snapshot_round_trip_keeps_the_ranking():
    _, si, qh = _live_index(17, bm25(K1, B))
    si.delete([3, 120])
    restored = restore_segmented(serialize_segmented(si))
    assert restored.ranking == bm25(K1, B)
    assert restored.live_token_count == si.live_token_count
    np.testing.assert_array_equal(restored._doc_row, si._doc_row)
    a, b = _served(si, qh), _served(restored, qh)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1].view(np.uint32),
                                  b[1].view(np.uint32))
    # a snapshot written before rankings were recorded restores cosine
    assert Ranking.from_meta(None) == TFIDF_COSINE


def test_bm25_paths_outside_its_engine_are_refused():
    from repro.serve.mesh import MeshConfig, MeshServer
    _, si, qh = _live_index(4, bm25(K1, B))
    with pytest.raises(ValueError, match="tf-idf cosine only"):
        MeshServer(si, MeshConfig(n_shards=1))
    with pytest.raises(NotImplementedError):
        si.view().topk(qh, 10, mode="dense")
    with pytest.raises(NotImplementedError):
        si.conjunctive(qh[0], 10, cap=64)
    with pytest.raises(ValueError):
        bm25(0.0, 0.4)
