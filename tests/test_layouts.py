"""The four paper representations: equivalence, sizes, access paths."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import layouts, query
from repro.core.layouts import REPRESENTATIONS


def all_indexes(host):
    return {
        "pr": layouts.build_coo(host),
        "pr-hash": layouts.build_coo(host, lookup="hash"),
        "or": layouts.build_csr(host),
        "or-hash": layouts.build_csr(host, lookup="hash"),
        "cor": layouts.build_compact_csr(host),
        "hor": layouts.build_blocked(host, block=32),
        "packed": layouts.build_packed_csr(host, block=32),
    }


def test_scoring_equivalent_across_representations(small_host, query_hashes):
    """Table 3: every representation answers queries identically."""
    cap = small_host.max_posting_len
    idx = all_indexes(small_host)
    ref = query.score_queries(idx["or"], jnp.asarray(query_hashes), k=10,
                              cap=cap)
    for name, ix in idx.items():
        r = query.score_queries(ix, jnp.asarray(query_hashes), k=10, cap=cap)
        np.testing.assert_allclose(np.asarray(r.scores),
                                   np.asarray(ref.scores), rtol=2e-3,
                                   atol=1e-5, err_msg=name)


def test_size_ordering_matches_paper(small_host):
    """ORIF must be smaller than PR (paper §4.1: W < N_d always)."""
    idx = all_indexes(small_host)
    assert idx["or"].posting_bytes() < idx["pr"].posting_bytes()
    assert idx["cor"].nbytes() <= idx["or"].nbytes()


def test_packed_beats_csr_at_realistic_density():
    """Delta+bitpack wins once posting lists amortize the block padding
    (paper-scale df ~ 300k; here df ~ 266 >> block)."""
    from repro.core import build
    from repro.text import corpus
    tc = corpus.generate(corpus.CorpusSpec(num_docs=2000, vocab=300,
                                           avg_distinct=40, seed=2))
    host = build.bulk_build(tc)
    orx = layouts.build_csr(host)
    pk = layouts.build_packed_csr(host, block=128)
    assert pk.posting_bytes() < 0.7 * orx.posting_bytes()


def test_lookup_btree_vs_hash(small_host, query_hashes):
    """Paper Table 2: B+tree and Hash lookups give identical term ids."""
    bt = layouts.build_csr(small_host, lookup="btree")
    hs = layouts.build_csr(small_host, lookup="hash")
    q = jnp.asarray(query_hashes[0])
    assert (bt.lookup_terms(q) == hs.lookup_terms(q)).all()
    # absent terms -> -1
    missing = jnp.asarray([4242424242, 7], dtype=jnp.uint32)
    assert (bt.lookup_terms(missing) == -1).all()
    assert (hs.lookup_terms(missing) == -1).all()


def test_blocked_contains(small_host):
    """HOR's GIN-analogue doc-membership probe with block skipping."""
    hor = layouts.build_blocked(small_host, block=32)
    t = 5
    tid_sorted = int(np.searchsorted(
        np.asarray(hor.sorted_hash),
        np.uint32(small_host.term_hashes[t])))
    s, e = small_host.offsets[t], small_host.offsets[t + 1]
    member = int(small_host.doc_ids[s])         # a doc containing term t
    docs_in = set(small_host.doc_ids[s:e].tolist())
    non_member = next(d for d in range(small_host.num_docs)
                      if d not in docs_in)
    tids = jnp.asarray([tid_sorted])
    assert bool(hor.contains(tids, jnp.int32(member))[0])
    assert not bool(hor.contains(tids, jnp.int32(non_member))[0])


def test_doc_deletion(small_host, query_hashes):
    """Document deletion (norm zeroing) removes docs from results."""
    from repro.core.direct_index import delete_docs
    ix = layouts.build_csr(small_host)
    cap = small_host.max_posting_len
    r = query.score_query(ix, jnp.asarray(query_hashes[0]), k=5, cap=cap)
    victim = r.doc_ids[0]
    new_norm = delete_docs(ix.docs.norm, jnp.asarray([victim]))
    ix2 = layouts.CsrIndex(
        offsets=ix.offsets, doc_ids=ix.doc_ids, tfs=ix.tfs, df=ix.df,
        lookup=ix.lookup,
        docs=layouts.DocTable(norm=new_norm, rank=ix.docs.rank),
        max_posting_len=ix.max_posting_len)
    r2 = query.score_query(ix2, jnp.asarray(query_hashes[0]), k=5, cap=cap)
    assert int(victim) not in np.asarray(r2.doc_ids).tolist()


def test_gather_postings_sorted_and_valid(small_host):
    ix = layouts.build_csr(small_host)
    tid = jnp.asarray([0, 1, -1])
    d, t, v = ix.gather_postings(tid, cap=small_host.max_posting_len)
    d0 = np.asarray(d[0])[np.asarray(v[0])]
    assert (np.diff(d0) > 0).all()          # doc-sorted within a term
    assert not np.asarray(v[2]).any()       # absent term -> all invalid


def _fill_blocks_reference(h, block):
    """The pre-vectorization per-term python packing loop, kept verbatim
    as the byte-level reference for ``build_blocked``'s fill."""
    order = np.argsort(h.term_hashes, kind="stable")
    lengths = np.diff(h.offsets)[order]
    nblocks = -(-lengths // block)
    nblocks = np.maximum(nblocks, (lengths > 0).astype(nblocks.dtype))
    block_offsets = np.zeros(h.num_terms + 1, dtype=np.int64)
    np.cumsum(nblocks, out=block_offsets[1:])
    NB = int(block_offsets[-1])
    bd = np.full((NB, block), -1, dtype=np.int32)
    bt = np.zeros((NB, block), dtype=np.float32)
    for newpos, old in enumerate(order):
        s, e = h.offsets[old], h.offsets[old + 1]
        n = e - s
        b0 = block_offsets[newpos]
        flat_d = bd[b0:block_offsets[newpos + 1]].reshape(-1)
        flat_t = bt[b0:block_offsets[newpos + 1]].reshape(-1)
        flat_d[:n] = h.doc_ids[s:e]
        flat_t[:n] = h.tfs[s:e]
    return block_offsets, bd, bt


@pytest.mark.parametrize("block", [32, 128])
def test_build_blocked_vectorized_fill_matches_loop(small_host, block):
    """The np-bucketing block packer (seal hot path) emits byte-identical
    blocks to the old per-term python loop."""
    ref_offs, ref_bd, ref_bt = _fill_blocks_reference(small_host, block)
    ix = layouts.build_blocked(small_host, block=block)
    np.testing.assert_array_equal(np.asarray(ix.block_offsets),
                                  ref_offs.astype(np.int32))
    assert np.asarray(ix.block_docs).tobytes() == ref_bd.tobytes()
    assert np.asarray(ix.block_tfs).tobytes() == ref_bt.tobytes()


def test_build_blocked_vectorized_fill_edge_cases():
    """Empty terms, empty corpus, single oversized term."""
    hashes = np.array([7, 3, 9], np.uint32)
    # term 1 (hash 3) empty; term 2 spans 3 blocks of 4
    offsets = np.array([0, 2, 2, 12], np.int64)
    doc_ids = np.arange(12, dtype=np.int32)
    h = layouts.PostingsHost(
        term_hashes=hashes, df=np.array([2, 0, 10], np.int32),
        offsets=offsets, doc_ids=doc_ids,
        tfs=np.ones(12, np.float32), num_docs=16,
        norm=np.ones(16, np.float32), rank=np.zeros(16, np.float32))
    ref_offs, ref_bd, ref_bt = _fill_blocks_reference(h, 4)
    ix = layouts.build_blocked(h, block=4)
    assert np.asarray(ix.block_docs).tobytes() == ref_bd.tobytes()
    assert np.asarray(ix.block_tfs).tobytes() == ref_bt.tobytes()
    # empty corpus
    h0 = layouts.PostingsHost(
        term_hashes=np.zeros(0, np.uint32), df=np.zeros(0, np.int32),
        offsets=np.zeros(1, np.int64), doc_ids=np.zeros(0, np.int32),
        tfs=np.zeros(0, np.float32), num_docs=0,
        norm=np.zeros(0, np.float32), rank=np.zeros(0, np.float32))
    ix0 = layouts.build_blocked(h0)
    assert ix0.block_docs.shape[0] == 0


def test_pad_packed_to_class_roundtrip(small_host, query_hashes):
    """A size-class-padded packed index answers queries identically to
    the unpadded build (inert padding blocks, quantized statics)."""
    pk = layouts.build_packed_csr(small_host)
    nb = int(pk.packed.shape[0])
    padded = layouts.pad_packed_to_class(
        pk, nb_pad=layouts.size_class(nb),
        w_pad=layouts.size_class(pk.num_terms, base=256),
        max_posting_len=layouts.size_class(pk.max_posting_len),
        words_per_block=layouts.size_class(pk.words_per_block, base=8),
        route_pairs_max=layouts.size_class(pk.route_pairs_max),
        route_span_max=layouts.size_class(pk.route_span_max, base=8))
    cap = small_host.max_posting_len
    ref = query.score_queries(pk, jnp.asarray(query_hashes), k=10, cap=cap)
    got = query.score_queries(padded, jnp.asarray(query_hashes), k=10,
                              cap=cap)
    np.testing.assert_array_equal(np.asarray(got.doc_ids),
                                  np.asarray(ref.doc_ids))
    np.testing.assert_allclose(np.asarray(got.scores),
                               np.asarray(ref.scores), rtol=1e-6)
    with pytest.raises(ValueError):
        layouts.pad_packed_to_class(pk, nb_pad=1, w_pad=1,
                                    max_posting_len=1, words_per_block=1,
                                    route_pairs_max=1, route_span_max=1)


def test_packed_rows_are_stored_dma_ready(small_host):
    """Packed word rows are stored lane-padded to 128 and the f16 tfs as
    u32 pair rows that decode back exactly; ``posting_bytes`` still
    counts the compressed format, not the lane padding."""
    pk = layouts.build_packed_csr(small_host)
    nb = int(pk.packed.shape[0])
    assert pk.packed.shape[1] == layouts.lane_width(pk.words_per_block)
    assert pk.packed.shape[1] % 128 == 0
    assert pk.tf_pairs.shape == (-(-nb // 2), pk.block)
    assert pk.tf_pairs.dtype == jnp.uint32
    assert np.all(np.asarray(pk.packed)[:, pk.words_per_block:] == 0)
    tfs = np.asarray(layouts.unpair_tfs(pk.tf_pairs,
                                        jnp.arange(nb, dtype=jnp.int32)))
    for b in (0, nb // 2, nb - 1):
        docs, t, valid = pk.unpack_block(jnp.int32(b))
        np.testing.assert_array_equal(np.asarray(t), np.where(
            np.asarray(valid), tfs[b], 0.0))
    assert pk.posting_bytes() == int(
        pk.block_offsets.nbytes + 3 * nb * 4
        + nb * (4 * pk.words_per_block + 2 * pk.block))


@pytest.mark.parametrize("nb", [1, 2, 5])
def test_pair_tf_rows_roundtrip(nb):
    """Odd and even block counts: every block's tfs come back exact."""
    rng = np.random.default_rng(nb)
    tfs = (rng.random((nb, 128)) * 500).astype(np.float16)
    pairs = layouts.pair_tf_rows(tfs)
    assert pairs.shape == (-(-nb // 2), 128) and pairs.dtype == np.uint32
    got = np.asarray(layouts.unpair_tfs(jnp.asarray(pairs),
                                        jnp.arange(nb, dtype=jnp.int32)))
    np.testing.assert_array_equal(got, tfs.astype(np.float32))


def _packed_reference(h, block, max_bits=32):
    """The per-block loop the vectorized packed builder replaced."""
    order = np.argsort(h.term_hashes, kind="stable")
    lengths = np.diff(h.offsets)[order]
    nblocks = np.maximum(-(-lengths // block), (lengths > 0).astype(np.int64))
    block_offsets = np.zeros(h.num_terms + 1, dtype=np.int64)
    np.cumsum(nblocks, out=block_offsets[1:])
    nb = int(block_offsets[-1])
    out = {"bits": np.zeros(nb, np.int32), "base": np.zeros(nb, np.int32),
           "count": np.zeros(nb, np.int32), "min": np.zeros(nb, np.int32),
           "max": np.full(nb, -1, np.int32),
           "tfs": np.zeros((nb, block), np.float16)}
    words = []
    for newpos, old in enumerate(order):
        s, e = int(h.offsets[old]), int(h.offsets[old + 1])
        docs = h.doc_ids[s:e].astype(np.int64)
        for k in range(int(nblocks[newpos])):
            lo, hi = k * block, min((k + 1) * block, len(docs))
            blk = docs[lo:hi]
            prev = int(docs[lo - 1]) if lo > 0 else -1
            deltas = np.diff(np.concatenate([[prev], blk]))
            width = min(max(1, int(deltas.max()).bit_length()), max_bits)
            padded = np.zeros(block, dtype=np.int64)
            padded[:len(deltas)] = deltas
            words.append(layouts._pack_block_np(padded, width, block))
            b = int(block_offsets[newpos]) + k
            out["bits"][b], out["base"][b] = width, prev
            out["count"][b] = len(blk)
            out["min"][b], out["max"][b] = blk[0], blk[-1]
            out["tfs"][b, :len(blk)] = h.tfs[s:e][lo:hi]
    wpb = max((len(w) for w in words), default=1)
    out["packed"] = np.zeros((nb, layouts.lane_width(wpb)), np.uint32)
    for i, w in enumerate(words):
        out["packed"][i, :len(w)] = w
    return out, wpb


def _edge_host():
    """Empty terms, a term spanning blocks, deltas near 2**31."""
    big = np.array([0, 5, 2**30, 2**31 - 2], np.int32)
    doc_ids = np.concatenate([np.array([1, 3], np.int32),
                              np.arange(0, 600, 2, dtype=np.int32), big])
    offsets = np.array([0, 2, 2, 302, 306], np.int64)
    n = len(doc_ids)
    return layouts.PostingsHost(
        term_hashes=np.array([9, 4, 7, 1], np.uint32),
        df=np.diff(offsets).astype(np.int32), offsets=offsets,
        doc_ids=doc_ids, tfs=(np.arange(n) % 37 + 1).astype(np.float32),
        num_docs=2**31 - 1, norm=np.ones(4, np.float32),
        rank=np.zeros(4, np.float32))


@pytest.mark.parametrize("case", ["small-32", "small-128", "edge-128",
                                  "empty"])
def test_build_packed_csr_matches_block_loop(small_host, case):
    """The vectorized packed builder writes the same bytes as the
    per-block loop: words, decode scalars, doc summaries and tfs."""
    if case == "empty":
        h = layouts.PostingsHost(
            term_hashes=np.zeros(0, np.uint32), df=np.zeros(0, np.int32),
            offsets=np.zeros(1, np.int64), doc_ids=np.zeros(0, np.int32),
            tfs=np.zeros(0, np.float32), num_docs=0,
            norm=np.zeros(0, np.float32), rank=np.zeros(0, np.float32))
        block = 128
    else:
        name, block = case.split("-")
        h = small_host if name == "small" else _edge_host()
        block = int(block)
    ref, wpb = _packed_reference(h, block)
    pk = layouts.build_packed_csr(h, block=block)
    assert pk.words_per_block == wpb
    assert np.asarray(pk.packed).tobytes() == ref["packed"].tobytes()
    for name, got in (("bits", pk.block_bits), ("base", pk.block_base),
                      ("count", pk.block_count), ("min", pk.block_min),
                      ("max", pk.block_max)):
        np.testing.assert_array_equal(np.asarray(got), ref[name], name)
    assert np.asarray(pk.tf_pairs).tobytes() == \
        layouts.pair_tf_rows(ref["tfs"]).tobytes()
