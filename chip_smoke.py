"""Drive the served query path once on a TPU and check every answer.

    python chip_smoke.py                 # one chip, the paper's collection
    python chip_smoke.py --chips 4       # the 4-shard MeshServer path only
    JAX_PLATFORMS=cpu python chip_smoke.py --docs 3000 --cpu
                                         # rehearsal: interpreted kernels

One chip: the paper's 1,004,721-page collection (``campaign.TIERS["1m"]``,
regenerated from its seed) is bulk-built into one sealed segment through
``SegmentedIndex.from_host`` under the default ``LayoutCostModel``; the
rest goes through ``add_batch`` with one ``seal()``, so queries see two
sealed segments and a non-empty delta.  A ``QueryServer`` with its
default engine (fused candidate kernels, compiled) answers 64 Table-7
queries of 1-4 high-df terms, and every response must equal
``LiveView.topk(engine="jnp")`` on the same pinned epoch: ids and
scores, ties included.

``--chips 4``: the same corpus sealed into four comparable segments,
served by ``MeshServer(MeshConfig(n_shards=4, topology="doc_stack"))``;
every response must equal a single-host ``QueryServer`` on the same
pinned view.

Earlier lines are plumbing figures (build, compile and per-request
times on the named device), not benchmark numbers.  The last line is
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU (unless ``--cpu``) the script exits non-zero first.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

QUERIES = 64
DELTA_DOCS = 16_384   # the campaign's delta capacity at the 1m tier


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    return 1


def _say(**kw) -> None:
    print(json.dumps(kw, sort_keys=True), flush=True)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--docs", type=int, default=None,
                    help="corpus size (default: the 1m tier's 1,004,721)")
    ap.add_argument("--cpu", action="store_true",
                    help="allow a CPU backend (interpreted kernels) for "
                         "a small rehearsal; never prints the ok line")
    return ap.parse_args(argv)


def _corpus(n_docs: int):
    """The 1m tier's corpus (same seed and batching as the campaign),
    cut to ``n_docs`` and split into (bulk, sealed tail, delta tail)."""
    import dataclasses

    from benchmarks import campaign
    from repro.core.build import TokenizedCorpus
    from repro.text import corpus

    spec = dataclasses.replace(campaign.TIERS["1m"], num_docs=n_docs)
    terms, counts, hashes = [], [], None
    for batch in corpus.stream_batches(spec, campaign.BATCH_DOCS["1m"]):
        terms.extend(batch.doc_term_ids)
        counts.extend(batch.doc_counts)
        hashes = batch.term_hashes
    delta = min(DELTA_DOCS, max(n_docs // 64, 8))
    cuts = (n_docs - delta - delta // 4, n_docs - delta // 4, n_docs)

    def part(lo, hi):
        return TokenizedCorpus(doc_term_ids=terms[lo:hi],
                               doc_counts=counts[lo:hi],
                               term_hashes=hashes, num_docs=hi - lo)
    return spec, delta, [part(lo, hi) for lo, hi in
                         zip((0,) + cuts[:-1], cuts)]


def _queries(view, n_docs: int, seed: int):
    """64 queries of 1-4 terms from the Table-7 df band."""
    import numpy as np

    from repro.configs.paper_index import PAPER
    from repro.text import corpus

    df = np.asarray(view.df, np.int64)
    hashes = np.asarray(view.hashes, np.uint32)
    rows = []
    for i, n_terms in enumerate(PAPER.query_terms):
        qs = corpus.sample_query_terms(
            df, hashes, QUERIES // len(PAPER.query_terms), n_terms,
            df_band=PAPER.query_df_band, num_docs=n_docs, seed=seed + i)
        rows.extend(qs)
    return rows


def _device_bytes(view) -> dict:
    out = {}
    for seg in view.segments:
        out[seg.layout] = out.get(seg.layout, 0) + int(seg.index.nbytes())
    return out


def _serve_all(server, rows):
    from repro.serve.metrics import percentiles

    tickets = [server.submit(r) for r in rows]
    while server.pending:
        server.pump(max_batches=1)
    resp = [t.result(timeout=0) for t in tickets]
    lat = percentiles([r.latency_us for r in resp])
    return resp, lat


def _check_equal(tag, got, want_ids, want_scores, epoch) -> str | None:
    import numpy as np

    if not got.ok:
        return f"{tag}: status {got.status}"
    if got.epoch != epoch:
        return f"{tag}: served epoch {got.epoch} != pinned {epoch}"
    gi, gs = np.asarray(got.doc_ids), np.asarray(got.scores)
    if not np.array_equal(gi, want_ids):
        return f"{tag}: ids {gi.tolist()} != {want_ids.tolist()}"
    if not np.array_equal(gs.view(np.uint32), want_scores.view(np.uint32)):
        return f"{tag}: scores {gs.tolist()} != {want_scores.tolist()}"
    return None


def _one_chip(args, device: dict) -> str | None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import build, size_model
    from repro.core.live_index import SegmentedIndex
    from repro.kernels import autotune, ops
    from repro.kernels.fused_decode_score import default_k_tile
    from repro.kernels.runtime import resolve_interpret
    from repro.serve.server import QueryServer, ServerConfig

    t0 = time.perf_counter()
    spec, delta, (bulk, sealed_tail, delta_tail) = _corpus(args.docs)
    t_gen = time.perf_counter() - t0
    host = build.bulk_build(bulk)
    si = SegmentedIndex.from_host(
        host, delta_doc_capacity=delta,
        layout_policy=size_model.LayoutCostModel())
    del host
    si.add_batch(sealed_tail)
    si.seal()
    si.add_batch(delta_tail)
    t_build = time.perf_counter() - t0
    view = si.view()
    mix = view.layout_mix()
    _say(phase="build", docs=si.num_docs, corpus_seed=spec.seed,
         vocab=spec.vocab, gen_s=t_gen, host_build_s=t_build,
         segments=view.num_segments, delta_docs=int(view.delta_n_docs),
         layout_mix=mix["counts"], device_index_bytes=_device_bytes(view))
    if si.num_docs != args.docs:
        return f"built {si.num_docs} docs, asked for {args.docs}"
    if view.num_segments < 2 or view.delta_n_docs < 1:
        return (f"want >= 2 sealed segments and a non-empty delta, got "
                f"{view.num_segments} / {view.delta_n_docs}")
    if "packed" not in [s.layout for s in view.segments]:
        return ("no packed segment under the default cost model: "
                f"{mix['reasons']}")

    server = QueryServer(si, ServerConfig(batch_size=8, k=10))
    cfg = server.config
    t0 = time.perf_counter()
    server.warmup()
    t_warm = time.perf_counter() - t0

    # the segment engine the server just ran, lowered again for proof
    # that the Mosaic kernel (not the interpreter) is on the path
    seg = max(view.segments, key=lambda s: s.size_class)
    tcfg = autotune.lookup(cfg.backend, int(seg.index.docs.num_docs),
                           seg.layout)
    lowered = ops.fused_segment_topk.lower(
        seg.index, jnp.zeros((cfg.batch_size, cfg.n_terms_budget),
                             jnp.uint32),
        jnp.zeros((cfg.batch_size, cfg.n_terms_budget), jnp.float32),
        jnp.ones((cfg.batch_size,), jnp.float32),
        jnp.int32(seg.doc_base), k_tile=tcfg.resolve_k_tile(cfg.k),
        cap=int(seg.index.max_posting_len),
        max_pairs=ops.default_max_pairs(
            seg.index, cfg.batch_size, cfg.n_terms_budget,
            int(seg.index.max_posting_len), tcfg.tile),
        tile=tcfg.tile, backend=cfg.backend, q_pad=tcfg.q_pad,
        reducer=tcfg.reducer)
    compiled_kernel = "tpu_custom_call" in lowered.as_text()
    interp = resolve_interpret(None)
    _say(phase="compile", warmup_s=t_warm, interpret=interp,
         tpu_custom_call=compiled_kernel, segment_layout=seg.layout,
         size_class=int(seg.size_class), k_tile=default_k_tile(cfg.k))
    if not args.cpu and (interp or not compiled_kernel):
        return "kernels are not compiled for the chip"

    rows = _queries(view, si.num_docs, seed=11)
    t0 = time.perf_counter()
    resp, lat = _serve_all(server, rows)
    t_serve = time.perf_counter() - t0
    pinned = server.refresh_view()
    # the oracle scores the same [batch, n_terms_budget] batches the
    # server assembled
    for lo in range(0, len(rows), cfg.batch_size):
        qb = np.zeros((cfg.batch_size, cfg.n_terms_budget), np.uint32)
        for i, row in enumerate(rows[lo:lo + cfg.batch_size]):
            qb[i, :len(row)] = row
        want = pinned.topk(qb, cfg.k, engine="jnp")
        for i, r in enumerate(resp[lo:lo + cfg.batch_size]):
            err = _check_equal(f"query {lo + i}", r,
                               np.asarray(want.doc_ids)[i],
                               np.asarray(want.scores)[i], pinned.epoch)
            if err:
                return err
    hits = int(sum(int((np.asarray(r.doc_ids) >= 0).sum()) for r in resp))
    _say(phase="serve", queries=len(rows), equal_to_jnp_oracle=len(rows),
         result_hits=hits, serve_s=t_serve, device=device["kind"],
         request_p50_us=lat["p50"], request_p99_us=lat["p99"],
         cache_hit_rate=server.cache.hit_rate)
    return None


def _four_chips(args, device: dict) -> str | None:
    import numpy as np

    from repro.core import compaction, size_model
    from repro.core.build import TokenizedCorpus
    from repro.core.live_index import SegmentedIndex
    from repro.serve.mesh import MeshConfig, MeshServer
    from repro.serve.server import QueryServer, ServerConfig

    t0 = time.perf_counter()
    spec, _, parts = _corpus(args.docs)
    quarter = -(-args.docs // 4)
    si = SegmentedIndex(
        term_hashes=parts[0].term_hashes, delta_doc_capacity=quarter,
        delta_posting_capacity=quarter * 4 * spec.avg_distinct,
        layout_policy=size_model.LayoutCostModel(),
        # four comparable runs are exactly what tiered compaction would
        # merge back into one; the mesh needs them apart
        policy=compaction.TieredPolicy(min_run=1 << 30))
    terms = [t for p in parts for t in p.doc_term_ids]
    counts = [c for p in parts for c in p.doc_counts]
    for lo in range(0, args.docs, quarter):
        hi = min(lo + quarter, args.docs)
        si.add_batch(TokenizedCorpus(
            doc_term_ids=terms[lo:hi], doc_counts=counts[lo:hi],
            term_hashes=parts[0].term_hashes, num_docs=hi - lo),
            refresh_norms=False)
        si.seal()
    si.refresh_norms()
    t_build = time.perf_counter() - t0
    view = si.view()
    _say(phase="build", docs=si.num_docs, segments=view.num_segments,
         segment_docs=[int(s.doc_span) for s in view.segments],
         layout_mix=view.layout_mix()["counts"], host_build_s=t_build)
    if view.num_segments < 4:
        return f"want >= 4 sealed segments, got {view.num_segments}"

    mesh = MeshServer(si, MeshConfig(n_shards=4, topology="doc_stack",
                                     batch_size=8, k=10,
                                     auto_handoff=False))
    pinned = mesh.serving_view
    single = QueryServer(si, ServerConfig(batch_size=8, k=10))
    t0 = time.perf_counter()
    mesh.warmup()
    single.warmup()
    t_warm = time.perf_counter() - t0
    rows = _queries(pinned, si.num_docs, seed=11)
    t0 = time.perf_counter()
    got, lat = _serve_all(mesh, rows)
    t_serve = time.perf_counter() - t0
    want, _ = _serve_all(single, rows)
    for i, (g, w) in enumerate(zip(got, want)):
        if not w.ok or w.epoch != pinned.epoch:
            return f"query {i}: single-host reference not on the pin"
        err = _check_equal(f"query {i}", g, np.asarray(w.doc_ids),
                           np.asarray(w.scores), pinned.epoch)
        if err:
            return err
    _say(phase="mesh", shards=4, queries=len(rows),
         equal_to_single_host=len(rows), warmup_s=t_warm, serve_s=t_serve,
         device=device["kind"], request_p50_us=lat["p50"],
         request_p99_us=lat["p99"])
    return None


def main(argv=None) -> int:
    args = _parse(argv)
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": args.chips}
    if devs[0].platform != "tpu" and not args.cpu:
        return _fail(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < args.chips:
        return _fail(f"--chips {args.chips} but {len(devs)} devices")
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from repro.kernels.runtime import enable_compile_cache
    cache = enable_compile_cache()
    if args.docs is None:
        from benchmarks import campaign
        args.docs = campaign.TIERS["1m"].num_docs
    _say(phase="device", platform=device["platform"], kind=device["kind"],
         devices=len(devs), jax=jax.__version__, docs=args.docs,
         compile_cache=cache)
    run = _four_chips if args.chips == 4 else _one_chip
    err = run(args, device)
    if err:
        return _fail(err)
    if args.cpu:
        print("chip_smoke: CPU rehearsal passed (no ok line off the chip)",
              file=sys.stderr)
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
