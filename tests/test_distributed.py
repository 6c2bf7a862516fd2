"""Distributed engine tests — run in a subprocess with 8 host devices
(XLA_FLAGS must be set before jax initializes; the main test process
must keep seeing 1 device)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.text import corpus
from repro.core import build, layouts, query
from repro.distributed import retrieval, compress, decode_attn, topk

mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))

tc = corpus.generate(corpus.CorpusSpec(num_docs=640, vocab=500,
                                       avg_distinct=30, seed=9))
host = build.bulk_build(tc)
ref_ix = layouts.build_csr(host)
qh = corpus.sample_query_terms(host.df, host.term_hashes, 3, 3,
                               num_docs=host.num_docs)

# 1) document-partitioned == single-node (scores AND doc sets)
ds = retrieval.build_doc_sharded(host, 8)
scorer = retrieval.make_doc_sharded_scorer(ds, mesh, "data", k=10)
for q in qh:
    vv, ids = scorer(jnp.asarray(q))
    ref = query.score_query(ref_ix, jnp.asarray(q), k=10,
                            cap=host.max_posting_len)
    np.testing.assert_allclose(np.asarray(vv), np.asarray(ref.scores),
                               rtol=1e-5)
    assert set(np.asarray(ids).tolist()) == \
        set(np.asarray(ref.doc_ids).tolist())

# 1b) document-partitioned FUSED Pallas engine == single-node
bs = retrieval.build_doc_sharded_blocked(host, 8)
fscorer = retrieval.make_doc_sharded_fused_scorer(bs, mesh, "data", k=10)
for q in qh:
    vv, ids = fscorer(jnp.asarray(q))
    ref = query.score_query(ref_ix, jnp.asarray(q), k=10,
                            cap=host.max_posting_len)
    np.testing.assert_allclose(np.asarray(vv), np.asarray(ref.scores),
                               rtol=1e-5)
    assert set(np.asarray(ids).tolist()) == \
        set(np.asarray(ref.doc_ids).tolist())

# 1c) PACKED document-partitioned fused engine: per-shard packed
#     rebuild (identical shard bounds, posting order, and block
#     boundaries as 1b) — must be BIT-identical (values and ids, ties
#     included) to the HOR fused engine under the same candidate-merge
#     tier; the ladder front door returns the same index + a reason
ps = retrieval.build_doc_sharded_packed(host, 8)
pscorer = retrieval.make_doc_sharded_fused_scorer(ps, mesh, "data", k=10)
for q in qh:
    pv, pi = pscorer(jnp.asarray(q))
    hv, hi = fscorer(jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(pv), np.asarray(hv))
    np.testing.assert_array_equal(np.asarray(pi), np.asarray(hi))
lad, reason = retrieval.build_doc_sharded_fused(host, 8, layout="packed")
assert isinstance(lad, retrieval.PackedDocShardedIndex), lad
assert reason == "explicit", reason
lad2, reason2 = retrieval.build_doc_sharded_fused(host, 8)
assert isinstance(lad2, retrieval.BlockedDocShardedIndex), lad2
assert reason2 == "default", reason2

# 2) term-partitioned == single-node
ts = retrieval.build_term_sharded(host, 8)
tscorer = retrieval.make_term_sharded_scorer(ts, mesh, "data", k=10)
for q in qh:
    tv, ti = tscorer(jnp.asarray(q))
    ref = query.score_query(ref_ix, jnp.asarray(q), k=10,
                            cap=host.max_posting_len)
    np.testing.assert_allclose(np.asarray(tv), np.asarray(ref.scores),
                               rtol=1e-5)

# 2b) term-partitioned FUSED Pallas engine == single-node (per-shard
#     fused partial scores -> [D] psum -> sharded candidate extraction
#     -> candidate merge)
tb = retrieval.build_term_sharded_blocked(host, 8)
tfscorer = retrieval.make_term_sharded_fused_scorer(tb, mesh, "data", k=10)
for q in qh:
    tv, ti = tfscorer(jnp.asarray(q))
    ref = query.score_query(ref_ix, jnp.asarray(q), k=10,
                            cap=host.max_posting_len)
    np.testing.assert_allclose(np.asarray(tv), np.asarray(ref.scores),
                               rtol=1e-5)
    assert set(np.asarray(ti).tolist()) == \
        set(np.asarray(ref.doc_ids).tolist())

# 2d) PACKED term-sharded fused engine: per-vocab-shard re-compression,
#     in-VMEM decode, [D] psum, sharded candidate extraction — must be
#     BIT-identical (values and ids, ties included) to the HOR
#     term-sharded engine, which shares its slicing and block geometry
tp = retrieval.build_term_sharded_packed(host, 8)
tpscorer = retrieval.make_term_sharded_fused_scorer(tp, mesh, "data", k=10)
for q in qh:
    pv, pi = tpscorer(jnp.asarray(q))
    hv, hi = tfscorer(jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(pv), np.asarray(hv))
    np.testing.assert_array_equal(np.asarray(pi), np.asarray(hi))
    ref = query.score_query(ref_ix, jnp.asarray(q), k=10,
                            cap=host.max_posting_len)
    np.testing.assert_allclose(np.asarray(pv), np.asarray(ref.scores),
                               rtol=1e-5)
    assert set(np.asarray(pi).tolist()) == \
        set(np.asarray(ref.doc_ids).tolist())

# 2e) cap truncation surfaces ACROSS shards: truncated_terms is psum'd
#     (like the multi-segment conjunctive sums per-segment counters),
#     and the capped ranking matches the capped single-node oracle
cap = 8
qt = qh[0]
dfg = np.asarray(host.df)
expect_trunc = sum(
    1 for h in np.unique(qt[qt != 0])
    for pos in [np.flatnonzero(host.term_hashes == h)]
    if len(pos) and dfg[pos[0]] > cap)
capped = retrieval.make_term_sharded_fused_scorer(
    tp, mesh, "data", k=10, cap=cap, return_stats=True)
(cv, ci), st = capped(jnp.asarray(qt))
assert st["truncated_terms"] == expect_trunc, st
ref_c = query.score_query(ref_ix, jnp.asarray(qt), k=10, cap=cap)
np.testing.assert_allclose(np.asarray(cv), np.asarray(ref_c.scores),
                           rtol=1e-5)

# 2c) term-sharded vs doc-sharded fused agreement on a 2x2 mesh: docs
#     partitioned over axis "x", vocabulary over axis "y" — the two
#     fused engines must return identical rankings
mesh22 = jax.sharding.Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                           ("x", "y"))
bs2 = retrieval.build_doc_sharded_blocked(host, 2)
tb2 = retrieval.build_term_sharded_blocked(host, 2)
dsc = retrieval.make_doc_sharded_fused_scorer(bs2, mesh22, "x", k=10)
tsc = retrieval.make_term_sharded_fused_scorer(tb2, mesh22, "y", k=10)
for q in qh:
    dv, di = dsc(jnp.asarray(q))
    tv, ti = tsc(jnp.asarray(q))
    np.testing.assert_allclose(np.asarray(dv), np.asarray(tv), rtol=1e-5)
    assert set(np.asarray(di).tolist()) == set(np.asarray(ti).tolist())

# 3) distributed top-k over a sharded score vector
fn = topk.sharded_topk(mesh, "data")(5)
scores = jnp.arange(64, dtype=jnp.float32)
v, i = fn(scores)
assert np.asarray(i).tolist() == [63, 62, 61, 60, 59]

# 3b) k exceeding the shard-local length (top_k needs k <= n): local
#     top-k is clamped and padded with -inf / -1 before the merge
fn = topk.sharded_topk(mesh, "data")(20)
v, i = fn(jnp.arange(64, dtype=jnp.float32))   # local length 8 < k=20
assert np.asarray(i)[:5].tolist() == [63, 62, 61, 60, 59]
assert np.asarray(v).tolist() == list(range(63, 43, -1))
fused_k = retrieval.make_doc_sharded_fused_scorer(bs, mesh, "data",
                                                  k=2 * host.num_docs // 8)
vv, ids = fused_k(jnp.asarray(qh[0]))   # k > docs-per-shard
ref = query.score_query(ref_ix, jnp.asarray(qh[0]),
                        k=2 * host.num_docs // 8,
                        cap=host.max_posting_len)
hits = np.asarray(ref.doc_ids) >= 0
np.testing.assert_allclose(np.asarray(vv)[hits],
                           np.asarray(ref.scores)[hits], rtol=1e-5)
assert set(np.asarray(ids)[hits].tolist()) == \
    set(np.asarray(ref.doc_ids)[hits].tolist())

# 4) int8 compressed grad mean ~ identity within quantization error
x = jnp.asarray(np.random.default_rng(0).normal(size=(128,))
                .astype(np.float32))
cm = jax.jit(jax.shard_map(
    lambda v: compress.quantized_psum_mean(v, "data", 8),
    mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
np.testing.assert_allclose(np.asarray(cm(x)), np.asarray(x), rtol=0.1,
                           atol=0.05)

# 5) split-K decode attention == single-device oracle
from repro.models.attention import decode_attention
rng = np.random.default_rng(1)
q = jnp.asarray(rng.normal(size=(2, 4, 1, 16)).astype(np.float32))
kc = jnp.asarray(rng.normal(size=(2, 2, 64, 16)).astype(np.float32))
vc = jnp.asarray(rng.normal(size=(2, 2, 64, 16)).astype(np.float32))
cl = jnp.asarray([50, 63], jnp.int32)
sk = decode_attn.splitk_decode_attention(mesh, "data")
for w in (0, 16):
    got = sk(q, kc, vc, cl, window=w)
    want = decode_attention(q, kc, vc, cl, window=w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=1e-5)

# 6) GSPMD decode attention with seq-sharded cache == oracle (the
#    long_500k cell's partitioning, small scale)
from jax.sharding import NamedSharding
kc_sh = jax.device_put(kc, NamedSharding(mesh, P(None, None, "data", None)))
vc_sh = jax.device_put(vc, NamedSharding(mesh, P(None, None, "data", None)))
got = jax.jit(decode_attention)(q, kc_sh, vc_sh, cl)
want = decode_attention(q, kc, vc, cl)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                           atol=1e-5)
print("DISTRIBUTED_ALL_OK")
"""


MIXED_STACK_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.text import corpus
from repro.core import build, compaction
from repro.core.build import TokenizedCorpus
from repro.core.live_index import SegmentedIndex
from repro.distributed import retrieval

mesh = jax.make_mesh((4,), ("data",))
tc = corpus.generate(corpus.CorpusSpec(num_docs=600, vocab=400,
                                       avg_distinct=20, seed=13))
si = SegmentedIndex(term_hashes=tc.term_hashes, delta_doc_capacity=128,
                    delta_posting_capacity=8192,
                    policy=compaction.TieredPolicy(min_run=100))
layouts_cycle = ["hor", "packed", "hor", "packed", "hor", "packed"]
for i, a in enumerate(range(0, 600, 100)):
    si.add_batch(TokenizedCorpus(tc.doc_term_ids[a:a+100],
                                 tc.doc_counts[a:a+100],
                                 tc.term_hashes, 100))
    si.seal(layout=layouts_cycle[i])
si.delete([3, 155, 470, 599])

stacks = retrieval.stack_segment_shards(si, 4)
assert {m.layout for m, _ in stacks.groups} == {"hor", "packed"}
scorer = retrieval.make_doc_sharded_segment_scorer(stacks, mesh, "data",
                                                   k=10)
qh = corpus.sample_query_terms(np.asarray(si._df), si.term_hashes, 4, 3,
                               num_docs=si.live_doc_count, seed=3)
for q in qh:
    vv, ids = scorer(jnp.asarray(q))
    ref = si.topk(q[None], k=10)
    # mixed hor+packed groups interleave doc ranges; the canonicalized
    # candidate merge still reproduces the single-node ranking EXACTLY
    # (ties included)
    np.testing.assert_array_equal(np.asarray(ids),
                                  np.asarray(ref.doc_ids)[0])
    np.testing.assert_allclose(np.asarray(vv),
                               np.asarray(ref.scores)[0], rtol=1e-5)
    assert not np.isin(np.asarray(ids), [3, 155, 470, 599]).any()
print("MIXED_STACK_SHARDED_OK")

# zero new jit entries on a same-class rebuild: seal one more segment
# whose content is IDENTICAL to an earlier batch (so every quantized
# static lands in an existing (size_class, layout) group), rebuild the
# stack at the newer epoch, and the warm compiled scorer is reused
snap = retrieval.stack_scorer_cache_sizes()
si.add_batch(TokenizedCorpus(tc.doc_term_ids[0:100], tc.doc_counts[0:100],
                             tc.term_hashes, 100))
si.seal(layout="packed")
stacks2 = retrieval.stack_segment_shards(si, 4)
assert stacks2.signature() == stacks.signature(), (
    stacks2.signature(), stacks.signature())
scorer2 = retrieval.make_doc_sharded_segment_scorer(stacks2, mesh, "data",
                                                    k=10)
for q in qh[:2]:
    vv, ids = scorer2(jnp.asarray(q))
    ref = si.topk(q[None], k=10)
    np.testing.assert_array_equal(np.asarray(ids),
                                  np.asarray(ref.doc_ids)[0])
assert retrieval.stack_scorer_cache_sizes() == snap, (
    snap, retrieval.stack_scorer_cache_sizes())
print("MIXED_STACK_CACHE_OK")
"""


def test_mixed_stack_sharded_serving():
    """Packed and mixed hor+packed sealed-segment stacks shard across 4
    host devices, answer bit-identically to the single-node live index,
    and a same-class stack rebuild reuses the warm compiled scorer
    (zero new jit entries) — the PR-job guard on the packed distributed
    tier."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", MIXED_STACK_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=500)
    assert "MIXED_STACK_SHARDED_OK" in out.stdout, out.stderr[-3000:]
    assert "MIXED_STACK_CACHE_OK" in out.stdout, out.stderr[-3000:]


EDGE_CASE_SCRIPT = r"""
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.core import build, layouts, query
from repro.core.build import TokenizedCorpus
from repro.distributed import retrieval

mesh = jax.make_mesh((2,), ("data",))

# engineered corpus: 8 terms with ascending hashes (hash-sorted order ==
# term id), 1024 docs == exactly 2 doc tiles == one tile per shard
H = np.array([10, 20, 30, 40, 50, 60, 70, 80], np.uint32)
D = 1024
docs, counts = [], []
for d in range(D):
    t, c = [0], [1]                      # term 0 in EVERY doc: deltas
    if 512 <= d < 640:                   #   of 1 -> 1-bit packed blocks
        t.append(6); c.append(5)         # term 6: tile-1 docs, strong tf
    if d in (0, 700):
        t.append(5); c.append(2)         # term 5: one block, gap of 700
    if 100 <= d < 110:
        t.append(3); c.append(1)         # term 3: last term of shard 0
    if 200 <= d < 210:
        t.append(4); c.append(1)         # term 4: first term of shard 1
    if 300 <= d < 330:
        t.append(2); c.append(1)
    if 900 <= d < 910:
        t.append(7); c.append(1)
    docs.append(np.asarray(t, np.int64))
    counts.append(np.asarray(c, np.int64))
host = build.bulk_build(TokenizedCorpus(docs, counts, H, D))
ref_ix = layouts.build_csr(host)

tb = retrieval.build_term_sharded_blocked(host, 2)
tp = retrieval.build_term_sharded_packed(host, 2)
# term 0's consecutive doc ids really did pack at width 1
assert (np.asarray(tp.block_bits)[np.asarray(tp.block_count) > 0] == 1
        ).any(), np.asarray(tp.block_bits)
sh = retrieval.make_term_sharded_fused_scorer(tb, mesh, "data", k=10)
sp = retrieval.make_term_sharded_fused_scorer(tp, mesh, "data", k=10)

# a query whose terms sit on BOTH sides of the vocab-shard boundary
# (term 3 = last term of shard 0, term 4 = first term of shard 1), plus
# the 1-bit and wide-delta terms
queries = [np.array([40, 50, 10], np.uint32),     # boundary straddle
           np.array([10, 60, 0], np.uint32),      # 1-bit + gap block
           np.array([70, 10, 0], np.uint32)]
for q in queries:
    hv, hi = sh(jnp.asarray(q))
    pv, pi = sp(jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(pv), np.asarray(hv))
    np.testing.assert_array_equal(np.asarray(pi), np.asarray(hi))
    ref = query.score_query(ref_ix, jnp.asarray(q), k=10,
                            cap=host.max_posting_len)
    np.testing.assert_allclose(np.asarray(pv), np.asarray(ref.scores),
                               rtol=1e-5)
    assert set(np.asarray(pi).tolist()) == \
        set(np.asarray(ref.doc_ids).tolist())
print("EDGE_PARITY_OK")

# 32-bit delta width: re-encode term 5's single block (deltas [1, 700])
# at the full 32-bit width — the format is width-agnostic, so the
# re-encoded index must answer bit-identically
spos = 1                 # term 5 (hash 60) is slot 1 of shard 1's vocab
blk = int(np.asarray(tp.block_offsets)[1, spos])
deltas = np.zeros(128, np.int64)
deltas[0], deltas[1] = 1, 700            # doc 0 (base -1), then doc 700
wide = layouts._pack_block_np(deltas, 32, 128)
wpb32 = len(wide)
pk = np.zeros((tp.packed.shape[0], tp.packed.shape[1], wpb32), np.uint32)
pk[:, :, :tp.packed.shape[2]] = tp.packed
pk[1, blk, :] = 0
pk[1, blk, :wpb32] = wide
bits = tp.block_bits.copy()
bits[1, blk] = 32
tp32 = dataclasses.replace(tp, packed=pk, block_bits=bits,
                           words_per_block=wpb32)
sp32 = retrieval.make_term_sharded_fused_scorer(tp32, mesh, "data", k=10)
for q in queries:
    pv, pi = sp(jnp.asarray(q))
    wv, wi = sp32(jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(wv), np.asarray(pv))
    np.testing.assert_array_equal(np.asarray(wi), np.asarray(pi))
print("EDGE_32BIT_OK")

# an all-tombstoned tile "winning" a shard-local top-k: kill every doc
# of shard 1's tile (512..1023) — exactly where term 6's strong hits
# live; the dead tile's candidates are all (-inf, -1) and must never
# displace live docs in the merge
norm_dead = host.norm.copy()
norm_dead[512:1024] = 0.0
host_dead = dataclasses.replace(host, norm=norm_dead)
tb_d = retrieval.build_term_sharded_blocked(host_dead, 2)
tp_d = retrieval.build_term_sharded_packed(host_dead, 2)
ref_d = layouts.build_csr(host_dead)
sh_d = retrieval.make_term_sharded_fused_scorer(tb_d, mesh, "data", k=10)
sp_d = retrieval.make_term_sharded_fused_scorer(tp_d, mesh, "data", k=10)
q6 = np.array([70, 10, 0], np.uint32)
for sc in (sh_d, sp_d):
    dv, di = sc(jnp.asarray(q6))
    di = np.asarray(di)
    assert not ((di >= 512) & (di < 1024)).any(), di
    ref = query.score_query(ref_d, jnp.asarray(q6), k=10,
                            cap=host.max_posting_len)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(ref.scores),
                               rtol=1e-5)
    assert set(di.tolist()) == set(np.asarray(ref.doc_ids).tolist())
# a query hitting ONLY the dead tile returns no hits at all
q_only = np.array([70, 0, 0], np.uint32)
dv, di = sp_d(jnp.asarray(q_only))
assert (np.asarray(di) == -1).all(), np.asarray(di)
print("EDGE_TOMBSTONE_OK")

# k greater than the shard-local candidate count (one 512-wide tile per
# shard, k_tile caps at 512): the merge clamps and pads with -inf / -1
k_big = 600
sp_k = retrieval.make_term_sharded_fused_scorer(tp, mesh, "data", k=k_big)
bv, bi = sp_k(jnp.asarray(queries[0]))
ref = query.score_query(ref_ix, jnp.asarray(queries[0]), k=k_big,
                        cap=host.max_posting_len)
hits = np.asarray(ref.doc_ids) >= 0
np.testing.assert_allclose(np.asarray(bv)[hits],
                           np.asarray(ref.scores)[hits], rtol=1e-5)
assert set(np.asarray(bi)[hits].tolist()) == \
    set(np.asarray(ref.doc_ids)[hits].tolist())
print("EDGE_KBIG_OK")
"""


def test_packed_term_sharded_edge_cases():
    """Engineered bit-width and boundary cases through the packed
    term-sharded fused path: 1-bit and 32-bit delta widths, query terms
    straddling the vocab-shard boundary, an all-tombstoned tile that
    would have won a shard-local top-k, and k exceeding the shard-local
    candidate count."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", EDGE_CASE_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=500)
    for marker in ("EDGE_PARITY_OK", "EDGE_32BIT_OK",
                   "EDGE_TOMBSTONE_OK", "EDGE_KBIG_OK"):
        assert marker in out.stdout, (marker, out.stderr[-3000:])


@pytest.mark.parametrize("n_dev", [8])
def test_distributed_suite(n_dev):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=500)
    assert "DISTRIBUTED_ALL_OK" in out.stdout, out.stderr[-3000:]


def test_smoke_cell_dryrun_on_host_mesh():
    """Lower+compile a smoke cell on a tiny 4-device mesh end to end —
    the same machinery the production dry-run uses."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    script = r"""
import jax
from repro import configs
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
for arch_id, shape_id in [("qwen3-0.6b", "train_4k"),
                          ("mixtral-8x7b", "decode_32k"),
                          ("pna", "full_graph_sm"),
                          ("xdeepfm", "serve_bulk")]:
    cell = configs.get_arch(arch_id).cell(shape_id, scale="smoke",
                                          mesh_axes=("data", "model"))
    sh = cell.make_shardings(mesh)
    with mesh:
        c = jax.jit(cell.fn, in_shardings=sh,
                    donate_argnums=cell.donate).lower(
            *cell.abstract_args).compile()
    assert c.memory_analysis() is not None
print("SMOKE_DRYRUN_OK")
"""
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=500)
    assert "SMOKE_DRYRUN_OK" in out.stdout, out.stderr[-3000:]
