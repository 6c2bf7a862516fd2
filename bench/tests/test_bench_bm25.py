"""The cell msmarco-passage-1of8.dev: its configuration, mix, ranking
and readers are found by name; the BM25 reference against a brute force;
the bfloat16 control fails; the check of the restored index; and the
cell rehearsed on the CPU at a tiny size."""
import json
import re
import time

import numpy as np
import pytest

from bench import control, corpus, deploy, run, trace_reduce, traffic
from bench import walk_bm25, window
from bench.tests import tiny
from bench.walk import WalkBytes

CELL = "msmarco-passage-1of8.dev"
METRICS = ["walk_bm25_ms_per_batch", "walk_bm25_roofline"]


@pytest.fixture
def tiny_shard(monkeypatch):
    """The shard's configuration cut to a size the CPU runs in seconds
    (2,400 passages of about 37 tokens over 3,000 terms)."""
    monkeypatch.setitem(tiny.SHAPES, "msmarco-passage-1of8", dict(
        num_docs=2400, vocab=3000, doc_len_median=37,
        segments=[1280, 512, 384, 200], delta_docs=24))
    return tiny.cell(CELL)


def test_the_cell_resolves_with_its_configuration_mix_and_readers():
    bench = run.load_benchmark()
    cell, entry, config, mix = run.resolve_cell(bench, CELL)
    assert cell["chips"] == 1 and entry["name"] == "msmarco-passage-1of8"
    assert config["ranking"] == "anserini_bm25" and config["k"] == 10
    ranking = run.load_ranking(config["ranking"])
    assert (ranking.K1, ranking.B) == (config["ranking_params"]["k1"],
                                       config["ranking_params"]["b"])
    assert config["num_docs"] == sum(config["segments"]) + \
        config["delta_docs"] == 1_105_228
    assert config["vocab"] == 2_660_824
    assert set(config["reduced_from"]) >= set(entry["reduced"])
    assert mix["terms"] == {"rule": "corpus_frequency", "skip_top": 4}
    assert sum(mix["lengths"].values()) == pytest.approx(1.0)
    names = [m["name"] for m in run.metrics_for(bench, CELL, "per_layer")]
    assert names == METRICS
    for name in names:
        assert callable(run.load_reader(name))
    e2e = [m["name"] for m in run.metrics_for(bench, CELL, "end_to_end")]
    assert {"setup_s", "qps", "latency_p50_ms"} <= set(e2e)


def brute_force(c, terms, k1, b):
    """BM25 of every document, one posting at a time."""
    n = c.num_docs
    df = c.df()
    dl = [float(c.tfs[c.doc_ptr[d]:c.doc_ptr[d + 1]].sum())
          for d in range(n)]
    avgdl = sum(dl) / n
    score = np.zeros(n)
    for d in range(n):
        lo, hi = c.doc_ptr[d], c.doc_ptr[d + 1]
        have = dict(zip(c.terms[lo:hi].tolist(), c.tfs[lo:hi].tolist()))
        for t in terms:
            if t in have:
                tf = have[t]
                idf = np.log(1 + (n - df[t] + 0.5) / (df[t] + 0.5))
                score[d] += idf * tf * (k1 + 1) / (
                    tf + k1 * (1 - b + b * dl[d] / avgdl))
    return score


def test_reference_matches_a_brute_force(tiny_shard):
    _, config, mix, _ = tiny_shard
    c = corpus.generate(dict(config, num_docs=300,
                             segments=[300], delta_docs=0), tiny.SEED)
    qs = traffic.queries(mix, c.df(), c.token_counts(), c.num_docs, 12,
                         tiny.SEED)
    ranking = run.load_ranking("anserini_bm25")
    ref = ranking.Reference(c, {int(t) for q in qs for t in q})
    for q in qs:
        want = brute_force(c, q.tolist(), ranking.K1, ranking.B)
        got = ref.answer(q, 10)
        np.testing.assert_allclose(got.score, want, rtol=1e-12, atol=0)
        order = np.lexsort((np.arange(len(want)), -want))
        best = order[:min(10, int((want > 0).sum()))]
        np.testing.assert_array_equal(got.ids, best)


def test_bfloat16_control_fails_the_limit(tiny_shard):
    _, config, mix, _ = tiny_shard
    got = control.read(config, dict(mix, sample=64), tiny.SEED)
    assert got["correct"] is False
    assert got["score_gap"] > 10 * config["limits"]["score_gap"]


def test_the_snapshot_names_the_ranking_and_a_cosine_index_is_refused(
        tiny_shard):
    from repro.core.live_index import SegmentedIndex
    from repro.serve import snapshot

    _, config, _, _ = tiny_shard
    ranking = run.load_ranking("anserini_bm25")
    c = corpus.generate(config, tiny.SEED)
    state = deploy.snapshot_state(config, c, tiny.SEED, ranking)
    index = snapshot.restore_segmented(state)
    ranking.after_restore(index)          # a BM25 index passes
    view = index.view()
    assert view.live_tokens == int(c.tfs.sum())
    assert view.avgdl == pytest.approx(c.tfs.sum() / c.num_docs)
    with pytest.raises(RuntimeError, match="not bm25"):
        ranking.after_restore(SegmentedIndex(term_hashes=c.hashes))
    with pytest.raises(RuntimeError, match="not bm25"):
        ranking.after_restore([])         # a program with no rankings


def test_length_row_bytes_count_the_tiles_a_batch_visits():
    # segment 0: term 1 in docs 0..299 (3 blocks, tiles 0) and term 2
    # in docs 0 and 1500 (one block spanning tiles 0..2); segment 1:
    # term 2 in doc 10 (tile 0)
    seg0 = (np.concatenate([np.arange(300), [0, 1500]]),
            np.concatenate([np.full(300, 1), [2, 2]]))
    seg1 = (np.array([10]), np.array([2]))
    rows = walk_bm25.LengthRowBytes([seg0, seg1], {1, 2, 3})
    per_tile = walk_bm25.TILE * walk_bm25.ROW_BYTES
    assert rows.batch([np.array([1])]) == 1 * per_tile
    assert rows.batch([np.array([2, 3])]) == (3 + 1) * per_tile
    assert rows.batch([np.array([1]), np.array([2])]) == (3 + 1) * per_tile
    assert rows.batch([np.array([3])]) == 0


def test_readers_read_a_tiny_rehearsal(tiny_shard):
    """A tiny deployment serves a short window; its batches and its
    index go to the readers with a device trace that holds the walk (a
    CPU trace has no device plane, so the ops are written in)."""
    _, config, mix, _ = tiny_shard
    dep = run.Deployment(config, mix, tiny.SEED, traced=True)
    due, queries, rows = dep.traffic(dict(mix, rate_qps=12.0), 1.0,
                                     tiny.SEED)
    dep.server.start()
    try:
        rec = window.drive(dep.server, rows, due, 1.0)
    finally:
        dep.server.stop()
    batches = run.scored_batches(rec, queries)
    assert batches
    terms = {int(t) for b in batches for q in b for t in q}
    dev = "/device:TPU:0"
    evs = [trace_reduce.Event(dev, trace_reduce.OPS_LINE,
                              "%pair_walk_bm25.1 = (f32[5,8,128]) "
                              "custom-call(), custom_call_target="
                              '"tpu_custom_call"', 1000.0 * i, 400.0, "")
           for i in range(len(batches))]
    evs.append(trace_reduce.Event(dev, trace_reduce.OPS_LINE,
                                  "%fusion.3 = f32[8] fusion()", 0.0,
                                  9e6, ""))
    ctx = run.Context(dep.server, rec, evs, 1.0, batches,
                      WalkBytes(dep.corpus, dep.segments, terms),
                      {"hbm_bytes_per_s": 819e9}, int(config["k"]))
    ms = run.load_reader("walk_bm25_ms_per_batch")(ctx)
    share = run.load_reader("walk_bm25_roofline")(ctx)
    assert ms == pytest.approx(400e-6)
    walk_only = 100.0 * sum(ctx.walk_bytes.batch(b, ctx.k)
                            for b in batches) / (
        len(batches) * 400e-9 * 819e9)
    assert walk_only < share < 100
    # a program without the BM25 walk (the parent) reads nothing
    ctx.trace_events = evs[-1:]
    assert run.load_reader("walk_bm25_ms_per_batch")(ctx) is None
    assert run.load_reader("walk_bm25_roofline")(ctx) is None


def test_traced_rehearsal_reaches_a_passing_comparison(tiny_shard):
    c, config, mix, bench = tiny_shard
    t = time.perf_counter()
    peaks = json.loads((run.BENCH / "peaks.json").read_text())
    res = run.execute(c, config, mix, tiny.SEED, 1.0, True, bench,
                      peaks["devices"]["TPU v5 lite"], t)
    assert res["correct"], res["checks"]
    assert 0 < res["checks"]["score_gap"]["value"] < 1e-5
    assert res["attempted"] == 12 and res["failed"] == 0
    # a CPU trace holds no device ops: the device readers read nothing
    assert not set(METRICS) & set(res["metrics"])


def test_walk_pattern_finds_only_the_bm25_walk():
    names = ["%pair_walk_bm25.1 = (f32[8]) custom-call()",
             "%pair_walk.1 = (f32[8]) custom-call()"]
    assert [n for n in names if re.search(walk_bm25.PATTERN, n)] == \
        names[:1]
