"""Kernel-geometry autotuner: bit-identity of tuned geometries, the
bitonic tile reducer, the tuning table, and the streaming build path."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build, layouts, query
from repro.core.live_index import SegmentedIndex
from repro.kernels import autotune, ops
from repro.kernels.fused_decode_score import (
    _check_reducer, _tile_topk, _tile_topk_bitonic, build_batched_pairs,
    default_k_tile, fused_topk_blocked_pallas)
from repro.text import corpus


@pytest.fixture(autouse=True)
def _clean_table():
    """Every test starts from an empty active table (= historical
    defaults) and restores whatever was active before."""
    prev = autotune.set_active(None)
    yield
    autotune.set_active(prev)


# ---------------------------------------------------------------------------
# bitonic reducer: bit-identical (value, doc id) vs successive maxima
# ---------------------------------------------------------------------------


def _reduce_pair(final, base, k_tile, tile):
    sv, si = _tile_topk(jnp.asarray(final), base, k_tile, tile)
    bv, bi = _tile_topk_bitonic(jnp.asarray(final), base, k_tile, tile)
    return (np.asarray(sv), np.asarray(si)), (np.asarray(bv),
                                              np.asarray(bi))


def _assert_bit_identical(final, base, k_tile, tile):
    (sv, si), (bv, bi) = _reduce_pair(final, base, k_tile, tile)
    # bit-identical: values by bit pattern (not approx), ids exactly
    np.testing.assert_array_equal(sv.view(np.uint32), bv.view(np.uint32))
    np.testing.assert_array_equal(si, bi)


def test_bitonic_engineered_multi_tile_ties():
    """Many lanes share the max value: both reducers must break ties
    toward the LOWEST lane (global doc id), in the same order."""
    q, tile, k_tile = 4, 256, 16
    final = np.full((q, tile), -np.inf, np.float32)
    final[:, ::7] = 1.0          # 37 tied lanes per row
    final[:, 128:136] = 2.5      # 8 tied maxima mid-tile
    final[1] = 0.25              # a full row of one value
    _assert_bit_identical(final, 512, k_tile, tile)


def test_bitonic_all_neg_inf_tile():
    """A garbage tile (every lane -inf) must yield id -1 everywhere."""
    final = np.full((3, 128), -np.inf, np.float32)
    (sv, si), (bv, bi) = _reduce_pair(final, 0, 8, 128)
    np.testing.assert_array_equal(si, -1)
    np.testing.assert_array_equal(bi, -1)
    np.testing.assert_array_equal(sv.view(np.uint32), bv.view(np.uint32))


def test_bitonic_requires_pow2_tile():
    with pytest.raises(ValueError):
        _tile_topk_bitonic(jnp.zeros((1, 96), jnp.float32), 0, 8, 96)


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # pragma: no cover - optional dep
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @st.composite
    def tile_cases(draw):
        tile = draw(st.sampled_from([64, 128, 256, 512]))
        q = draw(st.integers(1, 5))
        k_tile = draw(st.integers(1, tile))
        kind = draw(st.sampled_from(["random", "ties", "sparse"]))
        seed = draw(st.integers(0, 2**16))
        rng = np.random.default_rng(seed)
        if kind == "random":
            final = rng.standard_normal((q, tile)).astype(np.float32)
        elif kind == "ties":
            vals = rng.choice(np.float32([0.0, 0.5, 1.0, 2.0]),
                              size=(q, tile))
            final = vals.astype(np.float32)
        else:
            final = np.full((q, tile), -np.inf, np.float32)
            n_live = draw(st.integers(0, tile))
            idx = rng.choice(tile, size=n_live, replace=False)
            final[:, idx] = rng.standard_normal(
                (q, n_live)).astype(np.float32)
        base = draw(st.sampled_from([0, tile, 7 * tile]))
        return final, base, k_tile, tile

    @settings(max_examples=40, deadline=None)
    @given(case=tile_cases())
    def test_bitonic_bit_identical_property(case):
        """PROPERTY: for any tile content — random, heavy ties, mostly
        -inf — the bitonic partial sort returns bit-identical (value,
        global doc id) candidates to the successive-maxima loop."""
        _assert_bit_identical(*case)


# ---------------------------------------------------------------------------
# non-default tile geometry: k_tile clamp + engine parity
# ---------------------------------------------------------------------------


def test_default_k_tile_clamps_to_tile():
    assert default_k_tile(10) == 16
    assert default_k_tile(10, tile=256) == 16
    # k wider than a narrow tile: clamp, never exceed the tile width
    assert default_k_tile(300, tile=256) == 256
    assert default_k_tile(300, tile=256, k_pad=64) == 256


def test_k_tile_above_tile_rejected():
    from repro.kernels.fused_decode_score import _check_k_tile
    with pytest.raises(ValueError):
        _check_k_tile(512, 256)
    with pytest.raises(ValueError):
        _check_k_tile(0, 256)
    _check_k_tile(256, 256)  # boundary OK


def _small_index(layout="hor"):
    tc = corpus.generate(corpus.CorpusSpec(num_docs=700, vocab=900,
                                           avg_distinct=30, seed=13))
    host = build.bulk_build(tc)
    ix = (layouts.build_packed_csr(host) if layout == "packed"
          else layouts.build_blocked(host))
    qh = corpus.sample_query_terms(host.df, host.term_hashes, 4, 3,
                                   num_docs=host.num_docs, seed=5)
    return host, ix, qh


@pytest.mark.parametrize("layout", ["hor", "packed"])
def test_non_default_tile_ranks_identically(layout):
    """Regression for the default_k_tile/tile interaction: a tuned
    non-default tile (256 and 1024) must rank exactly like the default
    512 geometry."""
    host, ix, qh = _small_index(layout)
    cap = host.max_posting_len
    ref, _ = query.fused_score_queries(ix, jnp.asarray(qh), k=10, cap=cap,
                                       backend="xla")
    for tile in (256, 1024):
        tuned, _ = query.fused_score_queries(
            ix, jnp.asarray(qh), k=10, cap=cap, backend="xla",
            tune=autotune.TuneConfig(tile=tile))
        np.testing.assert_array_equal(np.asarray(ref.doc_ids),
                                      np.asarray(tuned.doc_ids))
        np.testing.assert_allclose(np.asarray(ref.scores),
                                   np.asarray(tuned.scores),
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("cfg", [
    autotune.TuneConfig(reducer="bitonic"),
    autotune.TuneConfig(k_pad=16),
    autotune.TuneConfig(k_pad=16, reducer="bitonic"),
    autotune.TuneConfig(q_pad=16),
    autotune.TuneConfig(k_tile=32),
])
def test_tuned_geometry_bit_parity(cfg):
    """Geometries that keep the tile width must be BIT-identical to the
    default config (identical candidates up to k_tile width)."""
    host, ix, qh = _small_index("hor")
    cap = host.max_posting_len
    ref, _ = query.fused_score_queries(ix, jnp.asarray(qh), k=10, cap=cap,
                                       backend="xla")
    tuned, _ = query.fused_score_queries(ix, jnp.asarray(qh), k=10,
                                         cap=cap, backend="xla", tune=cfg)
    np.testing.assert_array_equal(np.asarray(ref.doc_ids),
                                  np.asarray(tuned.doc_ids))
    np.testing.assert_array_equal(
        np.asarray(ref.scores).view(np.uint32),
        np.asarray(tuned.scores).view(np.uint32))


def test_active_table_changes_make_scorer_geometry():
    """Installing a tuned table changes the geometry make_scorer bakes
    in — and results stay identical to the default geometry."""
    host, ix, qh = _small_index("hor")
    cap = host.max_posting_len
    base = query.make_scorer(ix, k=10, cap=cap, engine="pallas",
                             backend="xla")(jnp.asarray(qh))
    table = autotune.TuningTable()
    table.put("xla", autotune.size_class_of(int(ix.docs.num_docs)), "hor",
              autotune.TuneConfig(reducer="bitonic", k_tile=32))
    prev = autotune.set_active(table)
    try:
        tuned = query.make_scorer(ix, k=10, cap=cap, engine="pallas",
                                  backend="xla")(jnp.asarray(qh))
    finally:
        autotune.set_active(prev)
    np.testing.assert_array_equal(np.asarray(base.doc_ids),
                                  np.asarray(tuned.doc_ids))
    np.testing.assert_array_equal(
        np.asarray(base.scores).view(np.uint32),
        np.asarray(tuned.scores).view(np.uint32))


# ---------------------------------------------------------------------------
# tuning table
# ---------------------------------------------------------------------------


def test_tuning_table_roundtrip(tmp_path):
    t = autotune.TuningTable()
    t.put("pallas", 2048, "hor",
          autotune.TuneConfig(tile=1024, q_pad=16))
    t.put("xla", 512, "packed", autotune.TuneConfig(reducer="bitonic"))
    p = tmp_path / "table.json"
    t.save(str(p))
    t2 = autotune.TuningTable.load(str(p))
    assert t2.get("pallas", 2048, "hor") == autotune.TuneConfig(
        tile=1024, q_pad=16)
    assert t2.get("xla", 512, "packed") == autotune.TuneConfig(
        reducer="bitonic")
    # schema check refuses foreign files
    bad = {"schema": "other/9", "entries": []}
    p2 = tmp_path / "bad.json"
    p2.write_text(json.dumps(bad))
    with pytest.raises(ValueError):
        autotune.TuningTable.load(str(p2))


def test_lookup_falls_back_to_smaller_class_then_default():
    t = autotune.TuningTable()
    cfg = autotune.TuneConfig(q_pad=16)
    t.put("pallas", autotune.size_class_of(1000), "hor", cfg)
    # bigger class inherits the nearest smaller tuned class
    assert t.lookup("pallas", 500_000, "hor") == cfg
    # different layout / backend fall through to the defaults
    assert t.lookup("pallas", 500_000, "packed") == autotune.DEFAULT_CONFIG
    assert t.lookup("xla", 500_000, "hor") == autotune.DEFAULT_CONFIG


def test_empty_table_resolves_to_historical_defaults():
    assert autotune.lookup("pallas", 123_456, "hor") == \
        autotune.DEFAULT_CONFIG
    assert autotune.DEFAULT_CONFIG.tile == 512
    assert autotune.DEFAULT_CONFIG.q_pad == 8
    assert autotune.DEFAULT_CONFIG.k_pad == 8
    assert autotune.DEFAULT_CONFIG.reducer == "successive"


def test_reducer_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_REDUCER", "bitonic")
    assert autotune.lookup("pallas", 1000, "hor").reducer == "bitonic"
    monkeypatch.setenv("REPRO_REDUCER", "nope")
    with pytest.raises(ValueError):
        autotune.lookup("pallas", 1000, "hor")


def test_autotune_index_selects_and_stores_winner():
    host, ix, qh = _small_index("hor")
    idf_w = jnp.log1p(
        host.num_docs / jnp.maximum(
            jnp.asarray(np.where(qh > 0, 3.0, 0.0)), 1.0))
    table = autotune.TuningTable()
    configs = [autotune.DEFAULT_CONFIG,
               autotune.TuneConfig(q_pad=16)]
    best, records = autotune.autotune_index(
        ix, jnp.asarray(qh), idf_w, k=10, backend="xla",
        configs=configs, reps=1, warmup=1, table=table)
    assert len(records) == 2
    assert all(r["median_s"] > 0 for r in records)
    assert {tuple(sorted(r["config"].items())) for r in records} == \
        {tuple(sorted(c.to_dict().items())) for c in configs}
    stored = table.get("xla", autotune.size_class_of(int(ix.docs.num_docs)),
                       "hor")
    assert stored == best


# ---------------------------------------------------------------------------
# streaming build: bounded-RAM path is exact
# ---------------------------------------------------------------------------


def _live_topk_ids(si, qh, k=10):
    r = si.topk(qh, k, backend="xla")
    return np.asarray(r.doc_ids), np.asarray(r.scores)


def test_streaming_build_matches_bulk_ingest():
    """stream_batches + deferred-norm add_batch + one final
    refresh_norms ranks bit-identically to per-batch refreshes of the
    same stream."""
    spec = corpus.CorpusSpec(num_docs=900, vocab=700, avg_distinct=25,
                             seed=21)

    def build_si(refresh_each):
        si = SegmentedIndex(delta_doc_capacity=256,
                            delta_posting_capacity=256 * 64)
        for b in corpus.stream_batches(spec, batch_docs=200):
            si.add_batch(b, refresh_norms=refresh_each)
        si.seal()
        si.refresh_norms()
        return si

    eager = build_si(True)
    deferred = build_si(False)
    assert eager.num_docs == deferred.num_docs == spec.num_docs
    qh = corpus.sample_query_terms(
        np.asarray(eager.view().df), np.asarray(eager.view().hashes),
        6, 3, num_docs=spec.num_docs, seed=9)
    ei, es = _live_topk_ids(eager, qh)
    di, ds = _live_topk_ids(deferred, qh)
    np.testing.assert_array_equal(ei, di)
    np.testing.assert_array_equal(es.view(np.uint32), ds.view(np.uint32))


def test_stream_batches_reproducible_for_fixed_batching():
    """The stream is a pure function of (spec, batch_docs): rerunning
    with the SAME batching replays the exact corpus.  (Changing
    batch_docs reseeds every draw — only distributional statistics are
    batching-independent; see the stream_batches docstring.)"""
    spec = corpus.CorpusSpec(num_docs=500, vocab=400, avg_distinct=20,
                             seed=4)
    a = list(corpus.stream_batches(spec, batch_docs=125))
    b = list(corpus.stream_batches(spec, batch_docs=125))
    assert sum(x.num_docs for x in a) == spec.num_docs
    for x, y in zip(a, b):
        for tx, ty in zip(x.doc_term_ids, y.doc_term_ids):
            np.testing.assert_array_equal(tx, ty)
        for cx, cy in zip(x.doc_counts, y.doc_counts):
            np.testing.assert_array_equal(cx, cy)


def test_live_view_with_tuned_table_matches_default():
    """A live index mixing sealed segments + delta must rank
    identically when the active table swaps every segment to a tuned
    geometry."""
    spec = corpus.CorpusSpec(num_docs=600, vocab=500, avg_distinct=22,
                             seed=17)
    si = SegmentedIndex(delta_doc_capacity=128,
                        delta_posting_capacity=128 * 64)
    for b in corpus.stream_batches(spec, batch_docs=150):
        si.add_batch(b)
    qh = corpus.sample_query_terms(
        np.asarray(si.view().df), np.asarray(si.view().hashes), 5, 3,
        num_docs=spec.num_docs, seed=2)
    base_i, base_s = _live_topk_ids(si, qh)
    table = autotune.TuningTable()
    for cls in {autotune.size_class_of(int(s.index.docs.num_docs))
                for s in si.segments()}:
        table.put("xla", cls, "hor",
                  autotune.TuneConfig(reducer="bitonic", k_tile=32))
    prev = autotune.set_active(table)
    try:
        tuned_i, tuned_s = _live_topk_ids(si, qh)
    finally:
        autotune.set_active(prev)
    np.testing.assert_array_equal(base_i, tuned_i)
    np.testing.assert_array_equal(base_s.view(np.uint32),
                                  tuned_s.view(np.uint32))


# ---------------------------------------------------------------------------
# routing budgets: the served budget never drops a real routing pair
# ---------------------------------------------------------------------------


def _full_vocab_candidates():
    """Every term of an engineered corpus (2600 docs, 80 terms, seed 1)
    queried at full cap: the batch routes every (block, tile) pair of
    the index, so ``route_pairs_max`` is reached exactly."""
    tc = corpus.generate(corpus.CorpusSpec(num_docs=2600, vocab=80,
                                           avg_distinct=20, seed=1))
    host = build.bulk_build(tc)
    ix = layouts.build_blocked(host)
    cap = host.max_posting_len
    th = host.term_hashes
    qh = jnp.asarray(th[th != 0][None, :])
    t_ids = jnp.where(qh != 0, ix.lookup_terms(qh), -1)
    m = min(max(-(-cap // ix.block), 1), max(ix.max_blocks_per_term, 1))
    cands = ops.expand_block_candidates(
        ix.block_offsets, t_ids, jnp.ones_like(t_ids, jnp.float32), m,
        ix.block, cap)
    return ix, qh, cap, cands


def test_default_max_pairs_is_exact_on_full_vocab_batch():
    """The served budget covers the full pair set (overflow 0) and is
    tight: one pair fewer drops exactly one real pair."""
    ix, qh, cap, (cb, cv, cq, cw, cc) = _full_vocab_candidates()
    tf, tcn, n_tiles = ops.routing_spans(ix, 512)

    def overflow_at(mp):
        *_, ovf = build_batched_pairs(
            cb, cv, cq, cw.astype(jnp.float32), tf, tcn, n_tiles, 1, mp,
            cand_cap=cc)
        return int(ovf)

    budget = ops.default_max_pairs(ix, *qh.shape, cap, 512)
    assert budget == ops.scaled_pairs_budget(ix, 512) == ix.route_pairs_max
    assert overflow_at(budget) == 0
    assert overflow_at(budget - 1) == 1


@pytest.mark.parametrize("tile", [256, 1024])
def test_default_max_pairs_covers_retuned_tile(tile):
    """Away from the route tile the span bound scales: still no drop."""
    ix, qh, cap, (cb, cv, cq, cw, cc) = _full_vocab_candidates()
    tf, tcn, n_tiles = ops.routing_spans(ix, tile)
    *_, ovf = build_batched_pairs(
        cb, cv, cq, cw.astype(jnp.float32), tf, tcn, n_tiles, 1,
        ops.default_max_pairs(ix, *qh.shape, cap, tile), cand_cap=cc)
    assert int(ovf) == 0


def test_live_view_full_vocab_no_silent_drop():
    """LiveView.topk over a query naming every term processes the FULL
    pair set (overflow 0), at the default geometry and at a retuned
    tile, with the same ranking; the stats-free path routes the summed
    overflow through the loud-overflow contract."""
    tc = corpus.generate(corpus.CorpusSpec(num_docs=700, vocab=150,
                                           avg_distinct=25, seed=2))
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=256,
                        delta_posting_capacity=256 * 64)
    si.add_batch(tc)
    si.seal()
    th = np.asarray(si.view().hashes)
    qh = th[th != 0][None, :].astype(np.uint32)
    ref, stats = si.topk(qh, 10, return_stats=True)
    assert stats["pair_overflow"] == 0
    tuned, stats = si.topk(qh, 10, tune=autotune.TuneConfig(tile=1024),
                           return_stats=True)
    assert stats["pair_overflow"] == 0
    np.testing.assert_array_equal(np.asarray(ref.doc_ids),
                                  np.asarray(tuned.doc_ids))
    quiet = si.topk(qh, 10)
    np.testing.assert_array_equal(np.asarray(ref.doc_ids),
                                  np.asarray(quiet.doc_ids))


def test_tuning_table_ignores_retired_pairs_per_step(tmp_path):
    """Tables stored while ``pairs_per_step`` was a geometry axis still
    load: the retired field is dropped, the rest of the entry kept."""
    p = tmp_path / "old.json"
    p.write_text(json.dumps({"schema": autotune.TUNE_SCHEMA, "entries": [
        {"backend": "xla", "size_class": 4096, "layout": "hor",
         "config": {"tile": 1024, "q_pad": 8, "k_pad": 8, "k_tile": None,
                    "reducer": "successive", "pairs_per_step": 2}}]}))
    t = autotune.TuningTable.load(str(p))
    assert t.get("xla", 4096, "hor") == autotune.TuneConfig(tile=1024)
    assert "pairs_per_step" not in autotune.TuneConfig().to_dict()


# ---------------------------------------------------------------------------
# bitonic reducer is interpret-only until the j == 1 exchange is
# Mosaic-legal
# ---------------------------------------------------------------------------


def test_bitonic_reducer_refused_on_compiled_lowering():
    _check_reducer("bitonic", True)          # interpret mode allowed
    _check_reducer("successive", False)      # compiled successive allowed
    with pytest.raises(NotImplementedError):
        _check_reducer("bitonic", False)
    # the kernel entry point enforces it at trace time, before any
    # Mosaic lowering can fail or miscompile
    with pytest.raises(NotImplementedError):
        fused_topk_blocked_pallas(
            jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 8), jnp.float32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.zeros((2, 8), jnp.float32), jnp.zeros((2,), jnp.int32),
            jnp.ones((16,), jnp.float32), jnp.zeros((16,), jnp.float32),
            jnp.ones((8,), jnp.float32), 16, 8, tile=16,
            reducer="bitonic", interpret=False)


def test_poisoned_table_bitonic_downgrades_on_compiled_lowering():
    """A tuning table carrying reducer='bitonic' for a compiled
    (non-interpret) lowering must not detonate at kernel entry: lookup
    downgrades the entry to 'successive' (counter + one-shot warning),
    on exact hits AND nearest-smaller-class inheritance, while
    interpret-capable backends keep the tuned reducer.  The env
    override bypasses the downgrade, so the kernel's hard guard stays
    the backstop."""
    import warnings

    from repro.obs.registry import GLOBAL

    t = autotune.TuningTable()
    t.put("pallas-tpu", 2048, "hor", autotune.TuneConfig(reducer="bitonic"))
    t.put("xla", 2048, "hor", autotune.TuneConfig(reducer="bitonic"))
    counter = GLOBAL.counter("autotune_bitonic_downgrade")
    c0 = counter.value
    autotune._BITONIC_WARNED = False
    with pytest.warns(RuntimeWarning, match="bitonic"):
        cfg = t.lookup("pallas-tpu", 2048, "hor")       # exact class
    assert cfg.reducer == "successive"
    with warnings.catch_warnings():
        warnings.simplefilter("error")                  # one-shot only
        cfg = t.lookup("pallas-tpu", 500_000, "hor")    # inherited class
    assert cfg.reducer == "successive"
    assert counter.value == c0 + 2
    # interpret-capable lowerings keep the tuned (bit-identical) reducer
    assert t.lookup("xla", 2048, "hor").reducer == "bitonic"
    # the downgrade never rewrites the stored entry
    assert t.get("pallas-tpu", 2048, "hor").reducer == "bitonic"

    # REPRO_REDUCER=bitonic bypasses table resolution entirely — the
    # kernel-entry hard guard still refuses the compiled lowering
    with pytest.raises(NotImplementedError):
        fused_topk_blocked_pallas(
            jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 8), jnp.float32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.zeros((2, 8), jnp.float32), jnp.zeros((2,), jnp.int32),
            jnp.ones((16,), jnp.float32), jnp.zeros((16,), jnp.float32),
            jnp.ones((8,), jnp.float32), 16, 8, tile=16,
            reducer="bitonic", interpret=False)
