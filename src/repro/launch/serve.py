"""Serving launcher: batched retrieval over the paper's index layouts.

``python -m repro.launch.serve --repr hor --docs 5000 --queries 64``

Builds a synthetic corpus, constructs the chosen index representation,
and serves batched queries through the jit scorer (optionally the
document-sharded distributed engine with --shards N on a host mesh).
Reports throughput and a latency histogram — the q_word/q_occ/q_doc
pipeline of paper §3.7 end to end.
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repr", default="hor",
                    choices=["pr", "or", "cor", "hor", "packed"])
    ap.add_argument("--docs", type=int, default=5000)
    ap.add_argument("--vocab", type=int, default=8000)
    ap.add_argument("--avg-terms", type=int, default=60)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--terms", type=int, default=3)
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--shards", type=int, default=0,
                    help=">0: document-sharded engine over a host mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from repro.core import build, layouts, query
    from repro.kernels.runtime import enable_compile_cache
    from repro.text import corpus

    enable_compile_cache()

    t0 = time.time()
    tc = corpus.generate(corpus.CorpusSpec(
        num_docs=args.docs, vocab=args.vocab, avg_distinct=args.avg_terms,
        seed=args.seed))
    host = build.bulk_build(tc)
    print(f"corpus: D={host.num_docs} W={host.num_terms} "
          f"P={host.num_postings} build={time.time() - t0:.2f}s")

    qh = corpus.sample_query_terms(host.df, host.term_hashes, args.queries,
                                   args.terms, num_docs=host.num_docs,
                                   seed=args.seed + 1)

    if args.shards > 0:
        from repro.distributed import retrieval as dist_ret
        if args.shards > len(jax.devices()):
            raise SystemExit(f"--shards {args.shards} but "
                             f"{len(jax.devices())} devices")
        mesh = jax.make_mesh((args.shards,), ("data",),
                             devices=jax.devices()[:args.shards])
        ds = dist_ret.build_doc_sharded(host, args.shards)
        scorer1 = dist_ret.make_doc_sharded_scorer(ds, mesh, "data",
                                                   k=args.topk)
        scorer = jax.jit(jax.vmap(scorer1))
        print(f"engine: doc-sharded x{args.shards}")
    else:
        builder = layouts.REPRESENTATIONS[args.repr]
        index = builder(host)
        print(f"engine: {args.repr} index={index.nbytes() / 1e6:.1f} MB")
        cap = max(host.max_posting_len, 1)
        scorer = query.make_scorer(index, k=args.topk, cap=cap)

    lat = []
    hits = 0
    for i in range(0, args.queries, args.batch):
        qb = jnp.asarray(qh[i:i + args.batch])
        t0 = time.time()
        res = scorer(qb)
        jax.tree.map(lambda x: x.block_until_ready(), res)
        lat.append((time.time() - t0) / qb.shape[0])
        ids = np.asarray(res[0] if isinstance(res, tuple) else res.doc_ids)
        hits += int((ids >= 0).any(axis=-1).sum())
    lat_us = np.array(lat[1:] or lat) * 1e6
    print(f"served {args.queries} queries; {hits} with hits; "
          f"p50={np.percentile(lat_us, 50):.0f}us "
          f"p99={np.percentile(lat_us, 99):.0f}us per query "
          f"(steady-state, batch={args.batch})")


if __name__ == "__main__":
    main()
