"""Million-page Zipfian scale campaign with tracked BENCH artifacts.

Builds a live index at three tiers — 10k / 100k / 1M documents — by
STREAMING the synthetic corpus through ``SegmentedIndex.add_batch``
(``text.corpus.stream_batches``: host RAM stays bounded by one batch no
matter the tier; norms are refreshed once after the final seal instead
of per batch, which is bit-identical and turns the quadratic rescan
into a single pass), then measures:

  build    docs/sec, wall seconds, peak RSS (ru_maxrss), segments,
           postings, compaction amplification
  autotune the kernel-geometry sweep (``kernels.autotune``) on the
           largest sealed segment, on the Pallas/interpret backend —
           the tier where per-grid-step overhead makes non-default
           geometry win; the winning table is installed + saved
  query    fused candidates engine p50/p99 per batch size and terms/
           query (plain-HLO ``backend="xla"`` lowering for CPU wall
           time), with analytic bytes/query from core.size_model
  serving  QueryServer micro-drive: request latency p50/p99, achieved
           QPS, batch fill

Each tier writes a schema-versioned ``BENCH_campaign_<tier>.json`` (see
``benchmarks.common.write_bench``); the autotune sweep writes
``BENCH_autotune.json`` and the winning ``TUNED_cpu.json`` table.  CI's
daily job runs the 100k tier; the 1M tier is the committed-artifact
campaign run.

  PYTHONPATH=src python -m benchmarks.campaign --tier 10k
  PYTHONPATH=src python -m benchmarks.campaign --tier all --out DIR
"""
from __future__ import annotations

import argparse
import dataclasses
import resource
import time

import numpy as np

from benchmarks import common
from repro.core import size_model
from repro.core.live_index import SegmentedIndex
from repro.kernels import autotune
from repro.text import corpus

# Tier specs keep the paper's posting-length REGIME (df of a frequent
# term ~ 0.3*D) while scaling docs; 1M matches the paper's D=1,004,721.
TIERS = {
    "10k": corpus.CorpusSpec(num_docs=10_000, vocab=4_000,
                             avg_distinct=40, seed=7),
    "100k": corpus.CorpusSpec(num_docs=100_000, vocab=20_000,
                              avg_distinct=48, seed=7),
    "1m": corpus.CorpusSpec(num_docs=1_004_721, vocab=50_000,
                            avg_distinct=40, seed=7),
}
BATCH_DOCS = {"10k": 5_000, "100k": 25_000, "1m": 50_000}
QUERY_REPS = {"10k": 20, "100k": 10, "1m": 5}
TUNE_REPS = {"10k": 3, "100k": 2, "1m": 1}
SERVE_REQUESTS = {"10k": 160, "100k": 96, "1m": 48}

# Interpret-mode probe: the Pallas kernel in interpret mode executes
# one Python step per routing pair, so the sweep runs on a small sealed
# segment (~2k-doc class) — per-grid-step overhead is exactly the cost
# the winning geometry amortizes, and ``TuningTable.lookup`` lets every
# LARGER size class inherit the winner until swept directly.
PROBE_SPEC = corpus.CorpusSpec(num_docs=1_500, vocab=600,
                               avg_distinct=25, seed=7)


def _peak_rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def build_streaming(spec: corpus.CorpusSpec, batch_docs: int,
                    delta_docs: int = 16_384,
                    layout_policy: size_model.LayoutCostModel | None = None,
                    ) -> tuple[SegmentedIndex, dict]:
    """Stream-build a sealed SegmentedIndex; returns (index, stats).

    ``layout_policy=None`` keeps the historical hor-everywhere build
    (bit-identical to pre-chooser campaigns); passing a
    ``LayoutCostModel`` routes every seal/compaction through the
    override ladder, and the converged mix lands in the artifact."""
    si = SegmentedIndex(delta_doc_capacity=delta_docs,
                        delta_posting_capacity=delta_docs * 64,
                        seal_layout="hor", layout_policy=layout_policy)
    rss0 = _peak_rss_mb()
    t0 = time.perf_counter()
    n_batches = 0
    for batch in corpus.stream_batches(spec, batch_docs):
        si.add_batch(batch, refresh_norms=False)
        n_batches += 1
    si.seal()
    si.refresh_norms()
    wall = time.perf_counter() - t0
    postings = sum(si.segment_postings())
    stats = {
        "docs": si.num_docs,
        "postings": int(postings),
        "batches": n_batches,
        "batch_docs": batch_docs,
        "wall_s": round(wall, 2),
        "docs_per_sec": round(si.num_docs / max(wall, 1e-9), 1),
        "segments": si.num_segments,
        "postings_merged": int(si.stats.postings_merged),
        "merge_amplification": round(
            si.stats.postings_merged / max(postings, 1), 2),
        "peak_rss_mb": round(_peak_rss_mb(), 1),
        "peak_rss_delta_mb": round(_peak_rss_mb() - rss0, 1),
    }
    return si, stats


def _layout_report(si: SegmentedIndex) -> dict:
    """Converged layout mix + per-segment byte roofline.

    For every sealed segment: the measured posting-array bytes, the
    EXACT hor bytes the same postings would occupy
    (``size_model.hor_posting_bytes_from_df`` over the segment's df),
    and their ratio — the campaign's acceptance check that
    chooser-selected packed segments really serve <= ~0.5x the HOR
    posting traffic per query, not just that the chooser fired."""
    mix = si.layout_mix()
    segs = []
    for seg in si.segments():
        hor_exact = size_model.hor_posting_bytes_from_df(
            np.asarray(seg.index.df))
        measured = seg.index.posting_bytes()
        rec = {
            "layout": seg.layout,
            "size_class": int(seg.size_class),
            "docs": int(seg.doc_span),
            "postings": int(seg.n_postings),
            "reason": seg.chooser_reason,
            "posting_bytes": int(measured),
            "hor_posting_bytes": int(hor_exact),
            "bytes_vs_hor": round(measured / max(hor_exact, 1), 3),
        }
        if seg.layout == "packed":
            # per ROUTED BLOCK: what a query actually streams from HBM
            # for each block its terms touch (same block boundaries in
            # both layouts, so this IS the bytes/query ratio) — the
            # array-total ratio above additionally counts rare-term
            # blocks no frequent-term query reads
            block = int(seg.index.block)
            per_packed = int(seg.index.words_per_block) * 4 + block * 2 + 12
            per_hor = block * 8 + 8
            rec["block_bytes_vs_hor"] = round(per_packed / per_hor, 3)
        elif seg.layout == "banded":
            # same per-routed-block roofline, but PER BAND: the packed
            # band's stride is band-local (the dense-body shape), so
            # its ratio can fall well below the monolithic-packed
            # floor; the HOR tail streams HOR blocks by construction
            ix = seg.index
            block = int(ix.packed.block)
            per_hor = block * 8 + 8
            per_packed = int(ix.packed.words_per_block) * 4 + block * 2 + 12
            rec["band_cut"] = int(seg.band_cut)
            rec["bands"] = {
                "packed": {
                    "terms": int(np.count_nonzero(
                        np.asarray(ix.packed.df))),
                    "posting_bytes": int(ix.packed.posting_bytes()),
                    "block_bytes_vs_hor": round(per_packed / per_hor, 3),
                },
                "hor": {
                    "terms": int(np.count_nonzero(np.asarray(ix.hor.df))),
                    "posting_bytes": int(ix.hor.posting_bytes()),
                    "block_bytes_vs_hor": 1.0,
                },
            }
        segs.append(rec)
    return {"counts": mix["counts"], "docs": mix["docs"],
            "postings": mix["postings"], "reasons": mix["reasons"],
            "segments": segs}


def _query_pool(view, num_queries: int, terms_per_query: int,
                seed: int = 11) -> np.ndarray:
    return corpus.sample_query_terms(
        np.asarray(view.df), np.asarray(view.hashes), num_queries,
        terms_per_query, num_docs=max(int(view.live_docs), 1), seed=seed)


def _sweep_segment(si: SegmentedIndex, k: int, reps: int,
                   backend: str) -> dict:
    """Sweep the geometry grid on the LARGEST sealed segment (the class
    every other segment compacts toward); install the winner in the
    active table."""
    view = si.view()
    seg = max(si.segments(), key=lambda s: int(s.index.docs.num_docs))
    qh, _, idf_w, _ = view._prep(_query_pool(view, 8, 3))
    table = autotune.get_active()
    best, records = autotune.autotune_index(
        seg.index, qh, idf_w, k, backend=backend, reps=reps, table=table)
    default_rec = next(r for r in records if r["is_default"])
    best_rec = next(r for r in records if r["config"] == best.to_dict())
    return {
        "backend": backend,
        "segment_docs": int(seg.index.docs.num_docs),
        "size_class": autotune.size_class_of(int(seg.index.docs.num_docs)),
        "layout": seg.layout,
        "best": best.to_dict(),
        "best_is_default": bool(best == autotune.DEFAULT_CONFIG),
        "default_median_s": default_rec["median_s"],
        "best_median_s": best_rec["median_s"],
        "speedup_vs_default": round(
            default_rec["median_s"] / max(best_rec["median_s"], 1e-12), 3),
        "records": records,
    }


def run_autotune_probe(k: int = 10, reps: int = 3) -> dict:
    """The CPU/interpret autotune demonstration: sweep the Pallas
    kernel IN INTERPRET MODE on a small sealed probe segment.  Interpret
    mode pays Python per grid step, so pairs-per-step unrolling and
    wider tiles (fewer steps) win decisively over the TPU-default
    geometry — the campaign artifact records the non-default choice."""
    si, _ = build_streaming(PROBE_SPEC, PROBE_SPEC.num_docs,
                            delta_docs=8_192)
    return _sweep_segment(si, k, reps, backend="pallas")


def run_autotune(si: SegmentedIndex, tier: str, k: int = 10,
                 backend: str = "xla") -> dict:
    """Per-tier sweep on the tier's own largest segment under the
    plain-HLO lowering (CPU wall-time representative)."""
    return _sweep_segment(si, k, TUNE_REPS[tier], backend=backend)


def run_queries(si: SegmentedIndex, tier: str, k: int = 10,
                backend: str = "xla") -> dict:
    """Fused-candidates latency sweep over batch sizes and query widths,
    plus the analytic candidate-traffic roofline per query."""
    view = si.view()
    reps = QUERY_REPS[tier]
    out: dict = {"backend": backend, "k": k, "sweeps": []}
    for n_terms in (1, 3):
        pool = _query_pool(view, 32, n_terms, seed=100 + n_terms)
        for bs in (1, 8):
            qb = pool[:bs]
            samples = common.time_samples(
                lambda q: view.topk(q, k, backend=backend), qb,
                reps=reps, warmup=2)
            s = common.summary_stats(samples)
            s.update(batch=bs, terms_per_query=n_terms,
                     us_per_query=round(s["p50_us"] / bs, 1))
            out["sweeps"].append(s)
            common.emit(f"campaign/{tier}/query_b{bs}_{n_terms}t",
                        s["p50_us"] / bs, common.latency_summary(samples))
    # candidate bytes/query: what the in-kernel top-k writes to HBM in
    # place of the dense [num_docs] score row, per sealed segment at its
    # tuned geometry (the §Roofline traffic term the campaign tracks)
    cand_bytes = 0
    post_bytes = 0
    for seg in si.segments():
        nd = int(seg.index.docs.num_docs)
        cfg = autotune.lookup(backend, nd, seg.layout)
        cand_bytes += size_model.candidate_bytes_per_query(
            nd, cfg.tile, cfg.resolve_k_tile(k))
        post_bytes += 8 * int(np.asarray(seg.index.docs.norm).shape[0])
    out["candidate_bytes_per_query"] = int(cand_bytes)
    out["dense_score_bytes_per_query"] = int(
        4 * sum(int(s.index.docs.num_docs) for s in si.segments()))
    return out


def run_serving(si: SegmentedIndex, tier: str, backend: str = "xla") -> dict:
    """Closed-loop QueryServer micro-drive against the campaign index."""
    from repro.serve import QueryServer, ServerConfig

    n_requests = SERVE_REQUESTS[tier]
    # trace every request: the tier artifact carries WHERE serving time
    # goes (queue wait vs kernel vs merge), not just the e2e percentile
    cfg = ServerConfig(batch_size=8, n_terms_budget=8, k=10,
                       backend=backend, trace_sample=1)
    server = QueryServer(si, cfg)
    view = si.view()
    pool = _query_pool(view, 64, 3, seed=23)
    qb = np.zeros((len(pool), cfg.n_terms_budget), np.uint32)
    qb[:, : pool.shape[1]] = pool
    server.warmup()
    rng = np.random.default_rng(5)
    server.start()
    try:
        t0 = time.perf_counter()
        done = 0
        while done < n_requests:
            # waves of 2 micro-batches: latency reflects batching +
            # scoring, not an unbounded closed-loop submit queue
            wave = min(2 * cfg.batch_size, n_requests - done)
            tickets = [server.submit(qb[rng.integers(len(qb))])
                       for _ in range(wave)]
            for t in tickets:
                t.result(timeout=600.0)
            done += wave
        wall = time.perf_counter() - t0
    finally:
        server.stop()
    m = server.metrics.summary()
    samples = server.metrics.latency.samples_us()
    s = common.summary_stats(samples)
    s.update(requests=n_requests,
             achieved_qps=round(n_requests / max(wall, 1e-9), 1),
             cache_hit_rate=m.get("cache_hit_rate", 0.0),
             stages=server.stage_summary())
    common.emit(f"campaign/{tier}/serving", s["p50_us"],
                common.latency_summary(samples))
    return s


def run_tier(tier: str, *, out_dir: str | None = None, k: int = 10,
             do_autotune: bool = True, do_serving: bool = True) -> str:
    spec = TIERS[tier]
    common.reset_records()
    print(f"# campaign tier={tier} docs={spec.num_docs}")
    # campaign tiers run with the adaptive chooser ON (defaults): every
    # 16k-doc seal clears min_packed_docs, so the roofline winner is
    # chosen at seal time and the artifact records the converged mix
    si, build_stats = build_streaming(
        spec, BATCH_DOCS[tier], layout_policy=size_model.LayoutCostModel())
    common.emit(f"campaign/{tier}/build", build_stats["wall_s"] * 1e6,
                f"docs_per_sec={build_stats['docs_per_sec']};"
                f"segments={build_stats['segments']};"
                f"peak_rss_mb={build_stats['peak_rss_mb']}")
    results: dict = {"build": build_stats,
                     "layout_mix": _layout_report(si)}
    mix = results["layout_mix"]
    packed_ratios = [s["bytes_vs_hor"] for s in mix["segments"]
                     if s["layout"] == "packed"]
    band_ratios = [s["bands"]["packed"]["block_bytes_vs_hor"]
                   for s in mix["segments"] if s["layout"] == "banded"]
    common.emit(
        f"campaign/{tier}/layout_mix", 0.0,
        f"counts={mix['counts']};"
        f"max_packed_bytes_vs_hor="
        f"{max(packed_ratios) if packed_ratios else 'n/a'};"
        f"max_banded_block_bytes_vs_hor="
        f"{max(band_ratios) if band_ratios else 'n/a'}")
    if do_autotune:
        tune = run_autotune(si, tier, k=k)
        results["autotune"] = tune
        common.emit(f"campaign/{tier}/autotune",
                    tune["best_median_s"] * 1e6,
                    f"speedup_vs_default={tune['speedup_vs_default']};"
                    f"best_is_default={tune['best_is_default']}")
    results["query"] = run_queries(si, tier, k=k)
    if do_serving:
        results["serving"] = run_serving(si, tier)
    return common.write_bench(
        f"campaign_{tier}", results=results,
        config={"spec": dataclasses.asdict(spec),
                "batch_docs": BATCH_DOCS[tier], "k": k},
        out_dir=out_dir)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tier", default="10k",
                    choices=sorted(TIERS) + ["all"])
    ap.add_argument("--out", default=None, help="artifact directory "
                    "(default benchmarks/artifacts)")
    ap.add_argument("--no-autotune", action="store_true")
    ap.add_argument("--no-serving", action="store_true")
    ap.add_argument("--no-probe", action="store_true",
                    help="skip the interpret-mode probe sweep")
    ap.add_argument("--save-table", default=None, metavar="PATH",
                    help="write the winning tuning table as JSON")
    args = ap.parse_args(argv)
    from repro.kernels.runtime import enable_compile_cache
    enable_compile_cache()
    tiers = sorted(TIERS) if args.tier == "all" else [args.tier]
    autotune_results = {}
    if not args.no_probe and not args.no_autotune:
        common.reset_records()
        probe = run_autotune_probe()
        autotune_results["probe_interpret"] = probe
        common.emit("campaign/probe/autotune_interpret",
                    probe["best_median_s"] * 1e6,
                    f"speedup_vs_default={probe['speedup_vs_default']};"
                    f"best_is_default={probe['best_is_default']}")
    for tier in tiers:
        path = run_tier(tier, out_dir=args.out,
                        do_autotune=not args.no_autotune,
                        do_serving=not args.no_serving)
        doc = common.read_bench(path)
        if "autotune" in doc["results"]:
            autotune_results[tier] = doc["results"]["autotune"]
    if autotune_results:
        common.reset_records()
        common.write_bench(
            "autotune",
            results={"tiers": autotune_results,
                     "table": autotune.get_active().to_dict()},
            config={"tiers": tiers}, out_dir=args.out)
    if args.save_table:
        autotune.get_active().save(args.save_table)


if __name__ == "__main__":
    main()
