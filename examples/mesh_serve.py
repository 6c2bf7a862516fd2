"""The distributed serving mesh end to end: a 4-shard MeshServer
fanning micro-batches over sharded segment stacks while ingest churn
drives cross-shard epoch handoffs, admission control and deadline
shedding guard a latency target, and two tenants share the tier
through isolated result-cache partitions — every response pinned to
one epoch and bit-identical to a single-host QueryServer over the
same view.

    PYTHONPATH=src python examples/mesh_serve.py          # 4 chips
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/mesh_serve.py      # 4 CPU devices
"""
import sys

import jax
import numpy as np

from repro.core import build, compaction
from repro.core.live_index import SegmentedIndex
from repro.serve import MeshConfig, MeshServer
from repro.text import corpus

if len(jax.devices()) < 4:
    sys.exit(f"mesh_serve: 4 shards need 4 devices, {len(jax.devices())} "
             "present (on CPU set XLA_FLAGS="
             "--xla_force_host_platform_device_count=4)")

spec = corpus.CorpusSpec(num_docs=2000, vocab=1000, avg_distinct=30, seed=5)
tc = corpus.generate(spec)
host = build.bulk_build(tc)


def batch(a, b):
    return build.TokenizedCorpus(tc.doc_term_ids[a:b], tc.doc_counts[a:b],
                                 tc.term_hashes, b - a)


# seed the live index: sealed runs are what the doc topology shards
si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=128,
                    delta_posting_capacity=8192,
                    policy=compaction.TieredPolicy(size_ratio=4.0,
                                                   min_run=4))
for a in range(0, 1200, 300):
    si.add_batch(batch(a, a + 300))
    si.seal()

mesh = MeshServer(si, MeshConfig(
    batch_size=8, n_terms_budget=8, k=10, trace_sample=1,
    n_shards=4, n_replicas=2,
    max_queue=64, deadline_us=60e6,              # the latency target
    auto_handoff=True, handoff_min_interval_s=0.01, seal_fill=0.5))
mesh.warmup()
print(f"mesh up: shards={mesh.config.n_shards} "
      f"replicas={len(mesh.replicas)} epoch={mesh.serving_epoch} "
      f"docs={si.num_docs} segments={si.num_segments}")

# traffic from two tenants over a finite pool (repeats -> cache hits,
# partitioned per tenant), with ingest churn between waves so the pump
# pays — and traces — cross-shard epoch handoffs mid-drive
pool = corpus.sample_query_terms(host.df, host.term_hashes, 24, 3,
                                 num_docs=host.num_docs, seed=9)
rng = np.random.default_rng(0)
tickets = []
for wave, a in enumerate(range(1200, 2000, 200)):
    for _ in range(24):
        tickets.append(mesh.submit(pool[rng.integers(len(pool))],
                                   tenant=f"tenant{len(tickets) % 2}"))
    mesh.add_batch(batch(a, a + 200))     # fans out to every replica
    if wave % 2:
        mesh.delete_docs([a - 7, a - 13])
    mesh.pump(max_batches=2)              # deterministic drive, no threads
    mesh.run_maintenance_once()
while mesh.pending:
    mesh.pump()
responses = [t.result(timeout=120.0) for t in tickets]

# shed both ways, deterministically: a burst past the admission bound
# resolves immediately as shed("admission"), and one ticket backdated
# past the 60s deadline sheds at batch pickup instead of being scored
burst = [mesh.submit(pool[0]) for _ in range(mesh.config.max_queue + 4)]
burst[4].t_submit -= 120.0
while mesh.pending:
    mesh.pump()
assert all(t.result(timeout=120.0).status in ("ok", "shed")
           for t in burst)

s = mesh.mesh_summary()
print(f"served {s['requests']} over {s['n_shards']} shards in "
      f"{s['batches']} batches across {s['epochs_served']} epochs "
      f"(now at epoch {s['epoch']})")
print(f"latency p50={s['p50_us'] / 1e3:.1f}ms p99={s['p99_us'] / 1e3:.1f}ms")
print(f"shed: {s['shed']} (rate={s['shed_rate']:.3f})")
print(f"handoffs: {s['handoffs']} "
      f"pause_p50={s['handoff_pause_us'].get('p50', 0.0) / 1e3:.1f}ms")
print("tenant cache partitions:")
for tenant, st in s["tenants"].items():
    print(f"  {tenant:<8} entries={st['entries']:<4} hits={st['hits']:<4} "
          f"misses={st['misses']}")

# shard fan-out stage breakdown: queue_wait / handoff / assemble /
# score (with per-shard dispatch + sync children) / respond
print("stage breakdown (p50/p99 us per sampled request):")
for stage, st in mesh.stage_summary().items():
    print(f"  {stage:<11} n={st['count']:<4} p50={st['p50']:>9.1f} "
          f"p99={st['p99']:>9.1f}")

# one traced response end to end: the shard fan-out is visible as
# shard_fanout/shard_sync children of the score span, and top-level
# stages sum exactly to the measured e2e latency
r = next(r for r in responses if r.trace is not None and r.status == "ok")
stages = r.trace.stage_durations()
chain = " -> ".join(f"{k}={v:.0f}us" for k, v in stages.items())
print(f"sample trace: {chain} "
      f"(sum={sum(stages.values()):.0f}us e2e={r.latency_us:.0f}us)")
fanout = [sp for sp in r.trace.spans if sp.name in ("shard_fanout",
                                                    "shard_sync")]
print("  score children: " + " ".join(
    f"{sp.name}={(sp.t1 - sp.t0) * 1e6:.0f}us" for sp in fanout))

# the consistency contract, demonstrated: over the now-quiescent mesh,
# responses at the pinned epoch == the single-host view.topk answer
# over the same view, bit for bit (ties included)
fresh = [mesh.submit(pool[i]) for i in range(4)]
mesh.pump()
view = mesh.serving_view
qb = np.stack([t.row for t in fresh])
oracle = view.topk(qb, k=mesh.config.k)
got = [t.result() for t in fresh]
assert all(g.epoch == view.epoch for g in got)
np.testing.assert_array_equal(
    np.stack([g.doc_ids for g in got]), np.asarray(oracle.doc_ids))
np.testing.assert_array_equal(
    np.stack([g.scores for g in got]), np.asarray(oracle.scores))
print("mesh == single-host QueryServer over the pinned view: "
      "bit-identical")

# the event log tells the whole serving + maintenance story in one
# stream: seal/compact next to handoff and shed
print(f"event counts: {si.events.counts()}")
for e in mesh.events(n=3):
    extra = {k: v for k, v in e.items()
             if k not in ("seq", "kind", "t_wall", "duration_us")}
    print(f"  #{e['seq']} {e['kind']}: {extra}")
