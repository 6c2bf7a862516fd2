"""Serving benchmark: offered-QPS sweeps over QueryServer and MeshServer.

A closed-loop driver paces single-query submissions at each offered
rate while the worker thread micro-batches them and a maintenance
thread seals/compacts behind pinned epochs; a background ingest stream
advances the epoch so the cache invalidation path is exercised, and the
query stream draws from a finite pool so repeats produce cache hits.

The mesh sweep repeats the drive against a ``MeshServer`` per shard
count, all in this process over ``jax.devices()`` (on CPU, launch with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``), with admission
control and deadline shedding armed, ingest churn forcing epoch
handoffs mid-drive, and per-tenant cache traffic.

Emits (CSV rows via benchmarks.common.emit):

  serving/qps_N          value = p50 request latency at offered rate N;
                         derived = p50/p99/mean (common.latency_summary,
                         the same helper churn.py reports with) +
                         achieved QPS, cache hit rate, batch fill,
                         epochs served
  serving/lifecycle      seals/compactions the maintenance thread ran
                         and the final segment count
  serving/mesh_sS_qps_N  value = p50 mesh request latency at offered
                         rate N over S shards; derived adds shed rate,
                         handoff count + pause percentiles, and the
                         per-stage breakdown

``--smoke`` (or run.py --smoke) shrinks both sweeps to a plumbing
check; the long sweeps are exercised by the slow-marked tests in
tests/test_serve.py (the daily full-suite job).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common
from repro.core import build, compaction
from repro.core.live_index import SegmentedIndex
from repro.serve import IndexMaintenance, QueryServer, ServerConfig
from repro.text import corpus


def _build_live_index(tc, holdback_frac=0.25, delta_docs=128):
    """Ingest all but a holdback slice (streamed during the drive)."""
    n = tc.num_docs
    first = int(n * (1 - holdback_frac))
    si = SegmentedIndex(
        term_hashes=tc.term_hashes, delta_doc_capacity=delta_docs,
        delta_posting_capacity=delta_docs * 64,
        policy=compaction.TieredPolicy(size_ratio=8.0, min_run=4))
    step = max(first // 8, 1)
    for a in range(0, first, step):
        b = min(a + step, first)
        si.add_batch(build.TokenizedCorpus(tc.doc_term_ids[a:b],
                                           tc.doc_counts[a:b],
                                           tc.term_hashes, b - a))
    return si, first


def run_sweep(rates, n_requests, *, pool_size=64, ingest_every=64,
              tc=None, host=None, seed=11):
    """Drive the server at each offered rate; returns one summary dict
    per rate (keys: offered_qps + ServerMetrics.summary fields)."""
    if tc is None or host is None:
        tc, host = common.bench_host()
    si, ingested = _build_live_index(tc)
    # every request sampled: the sweep reports a per-stage latency
    # breakdown (queue wait / assemble / score / respond) per offered
    # rate, so saturation shows WHERE the time went, not just that p99
    # grew
    cfg = ServerConfig(batch_size=8, n_terms_budget=8, k=10,
                       trace_sample=1)
    server = QueryServer(si, cfg)
    maint = IndexMaintenance(si, server.index_lock, seal_fill=0.5,
                             interval_s=0.001)
    server.warmup()
    pool = corpus.sample_query_terms(host.df, host.term_hashes,
                                     pool_size, 3,
                                     num_docs=host.num_docs, seed=seed)
    rng = np.random.default_rng(seed)
    holdback = list(range(ingested, tc.num_docs,
                          max((tc.num_docs - ingested) // 16, 1)))

    results = []
    server.start()
    maint.start()
    try:
        for rate in rates:
            server.metrics.reset()
            server.cache.reset_counters()
            server.stages.reset()
            gap = 1.0 / rate if rate > 0 else 0.0
            tickets = []
            next_ingest = ingest_every
            for i in range(n_requests):
                tickets.append(server.submit(pool[rng.integers(pool_size)]))
                if i == next_ingest and holdback:
                    # one ingest batch mid-drive: epoch advances, cache
                    # entries of older epochs become unreachable
                    a = holdback.pop(0)
                    b = min(a + 16, tc.num_docs)
                    with server.index_lock:
                        si.add_batch(build.TokenizedCorpus(
                            tc.doc_term_ids[a:b], tc.doc_counts[a:b],
                            tc.term_hashes, b - a))
                    next_ingest += ingest_every
                if gap:
                    time.sleep(gap)
            for t in tickets:
                t.result(timeout=120.0)
            s = server.metrics.summary()
            s["offered_qps"] = rate
            s["samples_us"] = server.metrics.latency.samples_us()
            s["stages"] = server.stage_summary()
            results.append(s)
    finally:
        maint.stop()
        server.stop()
    results.append({"lifecycle": {"maint_seals": maint.stats.seals,
                                  "maint_compactions":
                                      maint.stats.compactions,
                                  "segments": si.num_segments,
                                  "epoch": si.epoch}})
    return results


# -- mesh sweep ------------------------------------------------------------
#
# Every shard count runs in THIS process over the first S of
# ``jax.devices()``: on a chip machine a child process could not open
# the devices the parent already holds.  CPU callers get host devices
# by setting ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
# before launch; a shard count above the devices present is an error.


def _mesh_drive(n_shards: int, rates, n_requests: int, spec) -> list:
    """One MeshServer over ``n_shards`` devices, driven at each offered
    rate; returns one summary dict per rate."""
    import dataclasses

    import jax

    from repro.core import compaction
    from repro.serve import MeshConfig, MeshServer

    mesh = jax.make_mesh((n_shards,), ("shards",),
                         devices=jax.devices()[:n_shards])
    tc = corpus.generate(spec)
    host = build.bulk_build(tc)
    # ingest all but a holdback slice (streamed during the drive),
    # sealing per step so the doc topology has segment runs to shard
    n = tc.num_docs
    first = int(n * 0.75)
    si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=128,
                        delta_posting_capacity=128 * 64,
                        policy=compaction.TieredPolicy(size_ratio=8.0,
                                                       min_run=4))

    def part(a, b):
        return dataclasses.replace(tc, doc_term_ids=tc.doc_term_ids[a:b],
                                   doc_counts=tc.doc_counts[a:b],
                                   num_docs=b - a)

    step = max(first // 8, 1)
    for a in range(0, first, step):
        si.add_batch(part(a, min(a + step, first)))
        si.seal()
    cfg = MeshConfig(batch_size=8, n_terms_budget=8, k=10, trace_sample=1,
                     n_shards=n_shards, max_queue=64,
                     deadline_us=500_000.0, auto_handoff=True,
                     handoff_min_interval_s=0.02, seal_fill=0.5,
                     maintenance_interval_s=0.002)
    ms = MeshServer(si, cfg, mesh=mesh)
    ms.warmup()
    pool = corpus.sample_query_terms(host.df, host.term_hashes, 64, 3,
                                     num_docs=host.num_docs, seed=11)
    rng = np.random.default_rng(11)
    holdback = list(range(first, n, max((n - first) // 16, 1)))
    rows = []
    ms.start()
    try:
        for rate in rates:
            shed0 = ms.shed_counts()
            hand0 = ms.registry.histogram("mesh_handoff_pause_us").snapshot()
            ms.metrics.reset()
            ms.cache.reset_counters()
            ms.stages.reset()
            gap = 1.0 / rate
            tickets = []
            next_ingest = 24
            for i in range(n_requests):
                tickets.append(ms.submit(pool[rng.integers(64)],
                                         tenant="t%d" % (i % 4)))
                if i == next_ingest and holdback:
                    a = holdback.pop(0)
                    ms.add_batch(part(a, min(a + 16, n)))
                    next_ingest += 24
                time.sleep(gap)
            for t in tickets:
                t.result(timeout=120.0)
            m = ms.metrics.summary()
            shed1 = ms.shed_counts()
            hand1 = ms.registry.histogram("mesh_handoff_pause_us").snapshot()
            shed = {k: shed1[k] - shed0[k] for k in shed1}
            offered = m["requests"] + shed["total"]
            rows.append({
                "offered_qps": rate, "n_shards": n_shards,
                "offered": offered, "served": m["requests"],
                "p50_us": m["p50_us"], "p99_us": m["p99_us"],
                "achieved_qps": m["qps"], "shed": shed,
                "shed_rate": shed["total"] / offered if offered else 0.0,
                "handoffs": hand1["count"] - hand0["count"],
                "handoff_pause_p50_us": hand1.get("p50", 0.0),
                "handoff_pause_p99_us": hand1.get("p99", 0.0),
                "cache_hit_rate": m["cache_hit_rate"],
                "batch_fill": m["batch_fill"],
                "epochs_served": m["epochs_served"],
                "stages": ms.stage_summary()})
    finally:
        ms.stop()
    return rows


def run_mesh_sweep(shard_counts, rates, n_requests):
    """Offered-QPS x shard-count sweep over the MeshServer, in this
    process.  Returns the per-rate summary dicts; raises when a shard
    count exceeds the devices present."""
    import jax

    have = len(jax.devices())
    if max(shard_counts) > have:
        raise RuntimeError(
            f"serving: mesh sweep wants {max(shard_counts)} devices, "
            f"{have} present (on CPU set XLA_FLAGS="
            "--xla_force_host_platform_device_count=N before launch)")
    spec = common.SMOKE_SPEC if common.is_smoke() else common.BENCH_SPEC
    rows = []
    for n_shards in shard_counts:
        rows.extend(_mesh_drive(n_shards, rates, n_requests, spec))
    return rows


def _mesh_fragment(row: dict) -> str:
    return (f"p99={row['p99_us']:.1f}us "
            f"achieved_qps={row['achieved_qps']:.0f} "
            f"shed_rate={row['shed_rate']:.3f} "
            f"handoffs={row['handoffs']} "
            f"handoff_pause_p50={row['handoff_pause_p50_us']:.0f}us "
            f"hit_rate={row['cache_hit_rate']:.2f} "
            f"{_stage_fragment(row.get('stages', {}))}")


def _stage_fragment(stages: dict) -> str:
    """``score_p50=..us respond_p50=..us`` derived-column fragment —
    the dominant stages of the breakdown, CSV-greppable per rate (the
    mesh-only stages print only when the mesh sweep observed them)."""
    parts = []
    for stage in ("queue_wait", "handoff", "assemble", "score",
                  "respond", "shed"):
        st = stages.get(stage)
        if st and st.get("count"):
            parts.append(f"{stage}_p50={st['p50']:.1f}us")
    return " ".join(parts)


def main() -> None:
    tc, host = common.bench_host()
    smoke = common.is_smoke()
    rates = [100, 400] if smoke else [50, 200, 800, 3200]
    n_requests = 96 if smoke else 512
    results = run_sweep(rates, n_requests, tc=tc, host=host)
    artifact = []
    for s in results:
        if "lifecycle" in s:
            lc = s["lifecycle"]
            common.emit("serving/lifecycle", 0.0,
                        f"maint_seals={lc['maint_seals']} "
                        f"maint_compactions={lc['maint_compactions']} "
                        f"segments={lc['segments']} epoch={lc['epoch']}")
            artifact.append(s)
            continue
        common.emit(
            f"serving/qps_{s['offered_qps']}", s["p50_us"],
            f"{common.latency_summary(s['samples_us'])} "
            f"achieved_qps={s['qps']:.0f} "
            f"hit_rate={s['cache_hit_rate']:.2f} "
            f"batch_fill={s['batch_fill']:.2f} "
            f"epochs={s['epochs_served']} "
            f"{_stage_fragment(s.get('stages', {}))}")
        # raw per-request samples stay out of the artifact (the
        # summary percentiles carry the signal at 1/1000 the bytes)
        artifact.append({k: v for k, v in s.items() if k != "samples_us"})

    # sharded closed-loop sweep: offered QPS x shard count
    mesh_shards = [1, 2] if smoke else [1, 2, 4]
    mesh_rates = [100, 400] if smoke else [50, 200, 800]
    mesh_requests = 64 if smoke else 128
    mesh_rows = run_mesh_sweep(mesh_shards, mesh_rates, mesh_requests)
    for row in mesh_rows:
        common.emit(
            f"serving/mesh_s{row['n_shards']}_qps_{row['offered_qps']}",
            row["p50_us"], _mesh_fragment(row))
    common.write_bench(
        "serving",
        results={"sweep": artifact,
                 "mesh": {"rows": mesh_rows}},
        config={"rates": rates, "n_requests": n_requests,
                "mesh": {"shard_counts": mesh_shards,
                         "rates": mesh_rates,
                         "n_requests": mesh_requests},
                "smoke": smoke})


if __name__ == "__main__":
    from repro.kernels.runtime import enable_compile_cache
    enable_compile_cache()
    common.set_smoke()
    main()
