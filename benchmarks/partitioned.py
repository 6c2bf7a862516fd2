"""Doc- vs term-partitioned retrieval: the distribution crossover.

Runs both shard_map engines on an 8-device mesh over ``jax.devices()``
in this process (on CPU, launch with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; fewer devices
is an error) and reports per-query latency plus the ANALYTIC per-query
wire bytes at production scale — the quantity that decides the
sharding choice at 1000+ nodes:

  doc-partitioned : wire/query ~ shards * k * 8 B      (top-k merge)
  term-partitioned: wire/query ~ D * 4 B               ([D] psum)
"""
from __future__ import annotations

import time

from benchmarks.common import emit, is_smoke

SHARDS = 8


def main() -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import build
    from repro.distributed import retrieval
    from repro.text import corpus

    if len(jax.devices()) < SHARDS:
        raise RuntimeError(
            f"partitioned: needs {SHARDS} devices, {len(jax.devices())} "
            "present (on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={SHARDS})")
    mesh = jax.make_mesh((SHARDS,), ("data",),
                         devices=jax.devices()[:SHARDS])
    sizing = (dict(docs=1_500, vocab=600, avg=25, queries=8) if is_smoke()
              else dict(docs=8000, vocab=2000, avg=60, queries=32))
    tc = corpus.generate(corpus.CorpusSpec(
        num_docs=sizing["docs"], vocab=sizing["vocab"],
        avg_distinct=sizing["avg"], seed=4))
    host = build.bulk_build(tc)
    qh = corpus.sample_query_terms(host.df, host.term_hashes,
                                   sizing["queries"], 3,
                                   num_docs=host.num_docs, seed=5)
    for name, builder, mk in [
            ("doc", retrieval.build_doc_sharded,
             retrieval.make_doc_sharded_scorer),
            ("term", retrieval.build_term_sharded,
             retrieval.make_term_sharded_scorer),
            # fused engines per layout: the term-sharded tier runs the
            # compressed layout end to end (per-shard re-compression +
            # in-VMEM decode), so the crossover is measured per layout
            ("term_fused_hor", retrieval.build_term_sharded_blocked,
             retrieval.make_term_sharded_fused_scorer),
            ("term_fused_packed", retrieval.build_term_sharded_packed,
             retrieval.make_term_sharded_fused_scorer)]:
        scorer = mk(builder(host, SHARDS), mesh, "data", k=10)
        scorer(jnp.asarray(qh[0]))          # warm
        t0 = time.perf_counter()
        for q in qh:
            jax.block_until_ready(scorer(jnp.asarray(q)))
        us = (time.perf_counter() - t0) / len(qh) * 1e6
        emit(f"partitioned/{name}_sharded_{SHARDS}dev", us, "per_query")

    # analytic production-scale wire (1M docs, 256 shards, k=10)
    shards, k, docs = 256, 10, 1_004_721
    emit("partitioned/analytic/doc_wire_bytes", 0.0,
         f"per_query={shards * k * 8}")
    emit("partitioned/analytic/term_wire_bytes", 0.0,
         f"per_query={docs * 4};ratio={docs * 4 / (shards * k * 8):.0f}x")
    # per-layout posting-HBM bytes for the sharded fused engines live in
    # roofline.py (query_bytes/{doc,term}_sharded_{hor,packed} rows) —
    # this benchmark owns the latency/wire side of the crossover


if __name__ == "__main__":
    main()
