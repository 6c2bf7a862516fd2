"""Distributed index engine: document- vs term-partitioned sharding.

The paper's index is a single-node PSQL database; at cluster scale an
index shards one of two ways, and the choice decides the collective
pattern (this is the multi-pod story for the paper's own workload):

  * DOCUMENT-partitioned (``DocShardedIndex``): each shard holds the
    full vocabulary over a slice of documents.  A query broadcasts to
    all shards (cheap: a few u32 hashes), every shard evaluates
    q_word/q_occ/q_doc locally over its CSR slice, and the global
    answer is a distributed top-k merge (all-gather of k candidates per
    shard).  Collective bytes ~ S·k·8 per query — independent of corpus
    size.  This is how every production engine shards, and the ``pod``
    axis document-partitions across pods.

  * TERM-partitioned (``TermShardedIndex``): each shard owns a hash
    range of the vocabulary (whole posting lists).  A query touches only
    the shards owning its terms, but per-document partial scores must be
    psum'd across shards: collective bytes ~ D·4 per query batch.  Wins
    only when queries are single-term or the document space is tiny —
    we implement both so the benchmark can show the crossover.

Both are shard_map programs over stacked, padded per-shard CSR arrays
(the paper's OR layout, sliced and re-packed per shard).

The fused engines make the compressed (delta+bit-packed) layout a
first-class citizen of EVERY distributed path: the term-sharded tier
re-compresses each vocab shard's posting lists
(``build_term_sharded_packed``), the doc-sharded serving tier stacks
packed — or mixed hor+packed — sealed segments
(``stack_segment_shards``), and the bulk doc-sharded tier re-compresses
each document slice (``build_doc_sharded_packed``), in every case
decoding blocks IN VMEM inside the fused kernel so only compressed
bytes cross HBM per shard — the paper's §4.3 layout-determines-I/O
argument at cluster scale.  Which bulk layout to build is itself a
measured decision: ``build_doc_sharded_fused`` runs the layout ladder
(explicit arg > ``size_model.LayoutCostModel`` policy > "hor").
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import layouts, segments
from repro.core.layouts import PostingsHost
from repro.core.query import dedup_query_hashes, idf as idf_fn
from repro.distributed.topk import local_topk_merge

Array = jax.Array


# ---------------------------------------------------------------------------
# document-partitioned
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DocShardedIndex:
    """Stacked per-shard CSR arrays (leading dim = shard)."""
    sorted_hash: np.ndarray   # u32[S, W]      (vocab replicated per shard)
    df_local: np.ndarray      # i32[S, W]      per-shard document frequency
    df_global: np.ndarray     # i32[S, W]      global df (same every shard)
    offsets: np.ndarray       # i32[S, W+1]
    doc_ids: np.ndarray       # i32[S, Pmax]   LOCAL doc ids
    tfs: np.ndarray           # f32[S, Pmax]
    norm: np.ndarray          # f32[S, Dmax]
    doc_base: np.ndarray      # i32[S]         global id of local doc 0
    n_shards: int
    num_docs: int
    cap: int                  # max local posting length

    def device_arrays(self) -> dict:
        return {k: jnp.asarray(v) for k, v in dataclasses.asdict(self).items()
                if isinstance(v, np.ndarray)}


def build_doc_sharded(host: PostingsHost, n_shards: int) -> DocShardedIndex:
    order = np.argsort(host.term_hashes, kind="stable")
    sorted_hash = host.term_hashes[order]
    W = host.num_terms
    bounds = np.linspace(0, host.num_docs, n_shards + 1).astype(np.int64)
    term_of = np.repeat(np.arange(W, dtype=np.int64),
                        np.diff(host.offsets))

    sh_offsets, sh_docs, sh_tfs, sh_df = [], [], [], []
    dmax = int(np.max(np.diff(bounds)))
    cap = 0
    for s in range(n_shards):
        lo, hi = bounds[s], bounds[s + 1]
        m = (host.doc_ids >= lo) & (host.doc_ids < hi)
        t = term_of[m][np.argsort(term_of[m], kind="stable")]
        sel = np.argsort(term_of[m], kind="stable")
        docs = (host.doc_ids[m][sel] - lo).astype(np.int32)
        tfs = host.tfs[m][sel]
        df = np.bincount(t, minlength=W).astype(np.int32)
        # reorder terms into hash-sorted order (COR-style fused lookup)
        df_sorted = df[order]
        offs = np.zeros(W + 1, dtype=np.int64)
        np.cumsum(df_sorted, out=offs[1:])
        # postings re-packed in hash-sorted term order
        packed_docs = np.zeros(len(docs), np.int32)
        packed_tfs = np.zeros(len(docs), np.float32)
        src_offs = np.zeros(W + 1, dtype=np.int64)
        np.cumsum(df, out=src_offs[1:])
        for newpos, old in enumerate(order):
            a, bnd = src_offs[old], src_offs[old + 1]
            c = offs[newpos]
            packed_docs[c:c + bnd - a] = docs[a:bnd]
            packed_tfs[c:c + bnd - a] = tfs[a:bnd]
        sh_offsets.append(offs)
        sh_docs.append(packed_docs)
        sh_tfs.append(packed_tfs)
        sh_df.append(df_sorted)
        cap = max(cap, int(df_sorted.max()) if W else 0)

    pmax = max(len(x) for x in sh_docs)
    S = n_shards
    docs_a = np.zeros((S, pmax), np.int32)
    tfs_a = np.zeros((S, pmax), np.float32)
    offs_a = np.zeros((S, W + 1), np.int32)
    df_a = np.zeros((S, W), np.int32)
    norm_a = np.zeros((S, dmax), np.float32)
    for s in range(S):
        docs_a[s, :len(sh_docs[s])] = sh_docs[s]
        tfs_a[s, :len(sh_tfs[s])] = sh_tfs[s]
        offs_a[s] = sh_offsets[s]
        df_a[s] = sh_df[s]
        lo, hi = bounds[s], bounds[s + 1]
        norm_a[s, :hi - lo] = host.norm[lo:hi]
    df_glob = np.broadcast_to(host.df[order][None, :], (S, W)).copy()
    return DocShardedIndex(
        sorted_hash=np.broadcast_to(sorted_hash[None, :], (S, W)).copy(),
        df_local=df_a, df_global=df_glob.astype(np.int32),
        offsets=offs_a, doc_ids=docs_a, tfs=tfs_a, norm=norm_a,
        doc_base=bounds[:-1].astype(np.int32), n_shards=S,
        num_docs=host.num_docs, cap=cap)


def make_doc_sharded_scorer(index: DocShardedIndex, mesh: Mesh, axis: str,
                            k: int = 10):
    """jit fn(query_hashes u32[T]) -> (scores[k], global doc ids[k])."""
    arrs = index.device_arrays()
    cap = max(index.cap, 1)
    dmax = arrs["norm"].shape[1]
    num_docs = index.num_docs

    sharded = {n: P(axis) for n in
               ("sorted_hash", "df_local", "df_global", "offsets",
                "doc_ids", "tfs", "norm", "doc_base")}

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(sharded, P()), out_specs=(P(), P()), check_vma=False)
    def score(ix, qh):
        sq = {n: v[0] for n, v in ix.items()}    # drop shard dim
        qh = dedup_query_hashes(qh)
        pos = jnp.searchsorted(sq["sorted_hash"], qh).astype(jnp.int32)
        pos = jnp.clip(pos, 0, sq["sorted_hash"].shape[0] - 1)
        hit = (sq["sorted_hash"][pos] == qh) & (qh != 0)
        tid = jnp.where(hit, pos, -1)
        # idf uses GLOBAL df — scoring must match the single-node engine
        df_g = jnp.where(hit, sq["df_global"][pos], 0)
        w = idf_fn(df_g, num_docs)
        safe = jnp.maximum(tid, 0)
        d, v = segments.gather_segments(sq["doc_ids"], sq["offsets"], safe,
                                        cap, fill=-1)
        t, _ = segments.gather_segments(sq["tfs"], sq["offsets"], safe, cap,
                                        fill=0.0)
        valid = v & (tid >= 0)[:, None]
        weights = t * w[:, None]
        flat_d = jnp.where(valid, d, dmax).reshape(-1)
        acc = jnp.zeros((dmax + 1,), jnp.float32)
        acc = acc.at[flat_d].add(jnp.where(valid, weights, 0.0).reshape(-1),
                                 mode="drop")
        scores = acc[:dmax]
        qnorm = jnp.sqrt(jnp.maximum(jnp.sum(w * w), 1e-12))
        live = sq["norm"] > 0
        final = jnp.where(live & (scores > 0),
                          scores / (jnp.maximum(sq["norm"], 1e-12) * qnorm),
                          -jnp.inf)
        vv, ids = local_topk_merge(final, k, axis, sq["doc_base"])
        return vv, ids

    return jax.jit(lambda qh: score(arrs, qh))


# ---------------------------------------------------------------------------
# term-partitioned
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TermShardedIndex:
    sorted_hash: np.ndarray  # u32[S, Wmax]  (hash-range partition, padded)
    df: np.ndarray           # i32[S, Wmax]
    offsets: np.ndarray      # i32[S, Wmax+1]
    doc_ids: np.ndarray      # i32[S, Pmax]  GLOBAL doc ids
    tfs: np.ndarray          # f32[S, Pmax]
    norm: np.ndarray         # f32[D] (replicated)
    n_shards: int
    num_docs: int
    cap: int

    def device_arrays(self) -> dict:
        return {k: jnp.asarray(v) for k, v in dataclasses.asdict(self).items()
                if isinstance(v, np.ndarray)}


def build_term_sharded(host: PostingsHost, n_shards: int) -> TermShardedIndex:
    order = np.argsort(host.term_hashes, kind="stable")
    W = host.num_terms
    # contiguous hash-range partition of the sorted vocabulary
    bounds = np.linspace(0, W, n_shards + 1).astype(np.int64)
    wmax = int(np.max(np.diff(bounds)))
    sh = []
    pmax = 0
    for s in range(n_shards):
        terms = order[bounds[s]:bounds[s + 1]]
        lens = (host.offsets[terms + 1] - host.offsets[terms]).astype(np.int64)
        offs = np.zeros(wmax + 1, dtype=np.int64)
        np.cumsum(lens, out=offs[1:len(lens) + 1])
        offs[len(lens) + 1:] = offs[len(lens)]
        total = int(offs[len(lens)])
        docs = np.zeros(total, np.int32)
        tfs = np.zeros(total, np.float32)
        for i, t in enumerate(terms):
            a, bnd = host.offsets[t], host.offsets[t + 1]
            docs[offs[i]:offs[i + 1]] = host.doc_ids[a:bnd]
            tfs[offs[i]:offs[i + 1]] = host.tfs[a:bnd]
        hashes = np.full(wmax, 0xFFFFFFFF, np.uint32)
        hashes[:len(terms)] = host.term_hashes[terms]
        dfs = np.zeros(wmax, np.int32)
        dfs[:len(terms)] = host.df[terms]
        sh.append((hashes, dfs, offs, docs, tfs))
        pmax = max(pmax, total)
    S = n_shards
    out = TermShardedIndex(
        sorted_hash=np.stack([x[0] for x in sh]),
        df=np.stack([x[1] for x in sh]),
        offsets=np.stack([x[2] for x in sh]).astype(np.int32),
        doc_ids=np.zeros((S, pmax), np.int32),
        tfs=np.zeros((S, pmax), np.float32),
        norm=host.norm, n_shards=S, num_docs=host.num_docs,
        cap=int(host.max_posting_len))
    for s, (_, _, _, docs, tfs) in enumerate(sh):
        out.doc_ids[s, :len(docs)] = docs
        out.tfs[s, :len(tfs)] = tfs
    return out


def make_term_sharded_scorer(index: TermShardedIndex, mesh: Mesh, axis: str,
                             k: int = 10):
    arrs = index.device_arrays()
    cap = max(index.cap, 1)
    num_docs = index.num_docs

    sharded = {n: P(axis) for n in
               ("sorted_hash", "df", "offsets", "doc_ids", "tfs")}
    sharded["norm"] = P()

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(sharded, P()), out_specs=(P(), P()), check_vma=False)
    def score(ix, qh):
        sq = {n: (v[0] if n != "norm" else v) for n, v in ix.items()}
        qh = dedup_query_hashes(qh)
        pos = jnp.searchsorted(sq["sorted_hash"], qh).astype(jnp.int32)
        pos = jnp.clip(pos, 0, sq["sorted_hash"].shape[0] - 1)
        hit = (sq["sorted_hash"][pos] == qh) & (qh != 0)
        tid = jnp.where(hit, pos, -1)       # terms NOT on this shard miss
        df = jnp.where(hit, sq["df"][pos], 0)
        w = idf_fn(df, num_docs)
        safe = jnp.maximum(tid, 0)
        d, v = segments.gather_segments(sq["doc_ids"], sq["offsets"], safe,
                                        cap, fill=-1)
        t, _ = segments.gather_segments(sq["tfs"], sq["offsets"], safe, cap,
                                        fill=0.0)
        valid = v & (tid >= 0)[:, None]
        flat_d = jnp.where(valid, d, num_docs).reshape(-1)
        acc = jnp.zeros((num_docs + 1,), jnp.float32)
        acc = acc.at[flat_d].add(
            jnp.where(valid, t * w[:, None], 0.0).reshape(-1), mode="drop")
        partial = acc[:num_docs]
        # THE term-partitioned cost: a full [D] psum across shards
        scores = jax.lax.psum(partial, axis)
        qn2 = jax.lax.psum(jnp.sum(w * w), axis)
        qnorm = jnp.sqrt(jnp.maximum(qn2, 1e-12))
        live = sq["norm"] > 0
        final = jnp.where(live & (scores > 0),
                          scores / (jnp.maximum(sq["norm"], 1e-12) * qnorm),
                          -jnp.inf)
        vv, ii = jax.lax.top_k(final, k)
        return vv, ii

    return jax.jit(lambda qh: score(arrs, qh))


# ---------------------------------------------------------------------------
# document-partitioned, fused Pallas engine (HOR blocks per shard)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BlockedDocShardedIndex:
    """Stacked per-shard HOR/BlockedIndex arrays for the fused engine.

    Each shard re-packs its document slice into 128-lane posting blocks
    with the build-time (block -> doc-tile) routing cache, so the
    shard_map program can call the fused decode-and-score kernel locally
    and merge per-shard top-k — the distributed version of the one-HBM-
    pass read path.
    """
    sorted_hash: np.ndarray    # u32[S, W]
    df_global: np.ndarray      # i32[S, W]
    block_offsets: np.ndarray  # i32[S, W+1]
    block_docs: np.ndarray     # i32[S, NBmax, BLOCK]  LOCAL doc ids
    block_tfs: np.ndarray      # f32[S, NBmax, BLOCK]
    tile_first: np.ndarray     # i32[S, NBmax]
    tile_count: np.ndarray     # i32[S, NBmax]
    norm: np.ndarray           # f32[S, Dmax]
    doc_base: np.ndarray       # i32[S]
    n_shards: int
    num_docs: int              # global
    dmax: int                  # max local docs per shard
    tile: int
    max_blocks_per_term: int
    route_span_max: int
    route_pairs_max: int

    def device_arrays(self) -> dict:
        # NOT dataclasses.asdict: that deep-copies every (stacked, large)
        # numpy array on the host before the device transfer
        return {f.name: jnp.asarray(getattr(self, f.name))
                for f in dataclasses.fields(self)
                if isinstance(getattr(self, f.name), np.ndarray)}


def _doc_shard_subhosts(host: PostingsHost, n_shards: int):
    """Slice the corpus into per-doc-range PostingsHost sub-indexes
    (contiguous id ranges, LOCAL doc ids, term-major posting order) —
    the one slicing both bulk doc-sharded builders share, so the HOR
    and packed structures see identical per-shard block boundaries
    (that is what makes the two fused engines bit-identical)."""
    bounds = np.linspace(0, host.num_docs, n_shards + 1).astype(np.int64)
    dmax = int(np.max(np.diff(bounds)))
    W = host.num_terms
    term_of = np.repeat(np.arange(W, dtype=np.int64), np.diff(host.offsets))
    subs = []
    for s in range(n_shards):
        lo, hi = bounds[s], bounds[s + 1]
        m = (host.doc_ids >= lo) & (host.doc_ids < hi)
        order = np.lexsort((host.doc_ids[m], term_of[m]))
        docs = (host.doc_ids[m][order] - lo).astype(np.int32)
        tfs = host.tfs[m][order].astype(np.float32)
        df_l = np.bincount(term_of[m], minlength=W).astype(np.int32)
        offs = np.zeros(W + 1, dtype=np.int64)
        np.cumsum(df_l, out=offs[1:])
        subs.append(PostingsHost(term_hashes=host.term_hashes, df=df_l,
                                 offsets=offs, doc_ids=docs, tfs=tfs,
                                 num_docs=int(hi - lo),
                                 norm=host.norm[lo:hi],
                                 rank=host.rank[lo:hi]))
    return subs, bounds, dmax


def build_doc_sharded_blocked(host: PostingsHost, n_shards: int,
                              tile: int | None = None
                              ) -> BlockedDocShardedIndex:
    tile = tile or layouts.ROUTE_TILE
    subs, bounds, dmax = _doc_shard_subhosts(host, n_shards)
    W = host.num_terms
    shards = [layouts.build_blocked(sub) for sub in subs]

    block = shards[0].block
    nbmax = max(int(ix.block_docs.shape[0]) for ix in shards)
    S = n_shards
    bd = np.full((S, nbmax, block), -1, dtype=np.int32)
    bt = np.zeros((S, nbmax, block), dtype=np.float32)
    tf_arr = np.zeros((S, nbmax), dtype=np.int32)
    tc_arr = np.zeros((S, nbmax), dtype=np.int32)
    offs_a = np.zeros((S, W + 1), dtype=np.int32)
    norm_a = np.zeros((S, dmax), dtype=np.float32)
    for s, ix in enumerate(shards):
        nb = int(ix.block_docs.shape[0])
        bd[s, :nb] = np.asarray(ix.block_docs)
        bt[s, :nb] = np.asarray(ix.block_tfs)
        # routing spans vs the PADDED local doc space (uniform across
        # shards) so every shard's kernel sees the same tile grid
        tf_s, tc_s = layouts._block_tile_routing(
            np.asarray(ix.block_min), np.asarray(ix.block_max), dmax, tile)
        tf_arr[s, :nb] = tf_s
        tc_arr[s, :nb] = tc_s
        offs_a[s] = np.asarray(ix.block_offsets)
        lo, hi = bounds[s], bounds[s + 1]
        norm_a[s, :hi - lo] = host.norm[lo:hi]
    order = np.argsort(host.term_hashes, kind="stable")
    return BlockedDocShardedIndex(
        sorted_hash=np.broadcast_to(
            host.term_hashes[order][None, :], (S, W)).copy(),
        df_global=np.broadcast_to(
            host.df[order].astype(np.int32)[None, :], (S, W)).copy(),
        block_offsets=offs_a, block_docs=bd, block_tfs=bt,
        tile_first=tf_arr, tile_count=tc_arr, norm=norm_a,
        doc_base=bounds[:-1].astype(np.int32), n_shards=S,
        num_docs=host.num_docs, dmax=dmax, tile=tile,
        max_blocks_per_term=max(ix.max_blocks_per_term for ix in shards),
        route_span_max=max(int(np.max(tc_arr[s])) if nbmax else 0
                           for s in range(S)),
        route_pairs_max=max(int(np.sum(tc_arr[s])) for s in range(S)),
    )


@dataclasses.dataclass
class PackedDocShardedIndex:
    """Stacked per-shard delta+bit-packed arrays for the fused engine —
    the compressed twin of ``BlockedDocShardedIndex`` (the long-standing
    HOR-only gap of the bulk doc-sharded path).

    Each shard re-compresses its document slice: LOCAL doc-id deltas
    bit-packed at per-block minimal widths, f16 tfs in u32 pair rows
    (``layouts.pair_tf_rows``), the per-block (bits, base, count)
    decode scalars, and routing recomputed against
    the PADDED local doc space so every shard's kernel sees the same
    tile grid.  Cross-shard padding blocks carry ``bits=1, count=0`` —
    they decode to nothing, the same inert-padding trick the packed
    term-sharded and segment-stack paths use.
    """
    sorted_hash: np.ndarray    # u32[S, W]
    df_global: np.ndarray      # i32[S, W]
    block_offsets: np.ndarray  # i32[S, W+1]
    packed: np.ndarray         # u32[S, NBmax, lanes]  LOCAL-doc deltas
    tf_pairs: np.ndarray       # u32[S, ceil(NBmax/2), BLOCK]
    block_bits: np.ndarray     # i32[S, NBmax]  (1 on padding blocks)
    block_base: np.ndarray     # i32[S, NBmax]
    block_count: np.ndarray    # i32[S, NBmax]  (0 on padding blocks)
    tile_first: np.ndarray     # i32[S, NBmax]
    tile_count: np.ndarray     # i32[S, NBmax]
    norm: np.ndarray           # f32[S, Dmax]
    doc_base: np.ndarray       # i32[S]
    n_shards: int
    num_docs: int              # global
    dmax: int                  # max local docs per shard
    tile: int
    block: int
    words_per_block: int
    max_blocks_per_term: int
    route_span_max: int
    route_pairs_max: int

    def device_arrays(self) -> dict:
        return {f.name: jnp.asarray(getattr(self, f.name))
                for f in dataclasses.fields(self)
                if isinstance(getattr(self, f.name), np.ndarray)}


def build_doc_sharded_packed(host: PostingsHost, n_shards: int,
                             tile: int | None = None
                             ) -> PackedDocShardedIndex:
    """Per-doc-shard re-compression over the SAME slicing as
    ``build_doc_sharded_blocked`` — identical shard bounds, per-shard
    posting order, and block boundaries, so the packed fused engine is
    bit-identical to the HOR one under the candidate-merge tier."""
    tile = tile or layouts.ROUTE_TILE
    subs, bounds, dmax = _doc_shard_subhosts(host, n_shards)
    W = host.num_terms
    shards = [layouts.build_packed_csr(sub) for sub in subs]

    block = shards[0].block
    nbmax = max(int(ix.packed.shape[0]) for ix in shards)
    wpb = max(ix.words_per_block for ix in shards)
    S = n_shards
    pk = np.zeros((S, nbmax, layouts.lane_width(wpb)), dtype=np.uint32)
    tp = np.zeros((S, -(-nbmax // 2), block), dtype=np.uint32)
    bits_a = np.ones((S, nbmax), dtype=np.int32)   # padding decodes inert
    base_a = np.zeros((S, nbmax), dtype=np.int32)
    cnt_a = np.zeros((S, nbmax), dtype=np.int32)
    tf_arr = np.zeros((S, nbmax), dtype=np.int32)
    tc_arr = np.zeros((S, nbmax), dtype=np.int32)
    offs_a = np.zeros((S, W + 1), dtype=np.int32)
    norm_a = np.zeros((S, dmax), dtype=np.float32)
    for s, ix in enumerate(shards):
        nb = int(ix.packed.shape[0])
        pk[s, :nb, :ix.packed.shape[1]] = np.asarray(ix.packed)
        tp[s, :ix.tf_pairs.shape[0]] = np.asarray(ix.tf_pairs)
        bits_a[s, :nb] = np.asarray(ix.block_bits)
        base_a[s, :nb] = np.asarray(ix.block_base)
        cnt_a[s, :nb] = np.asarray(ix.block_count)
        # routing spans vs the PADDED local doc space (uniform across
        # shards), same as the HOR builder
        tf_s, tc_s = layouts._block_tile_routing(
            np.asarray(ix.block_min), np.asarray(ix.block_max), dmax, tile)
        tf_arr[s, :nb] = tf_s
        tc_arr[s, :nb] = tc_s
        offs_a[s] = np.asarray(ix.block_offsets)
        lo, hi = bounds[s], bounds[s + 1]
        norm_a[s, :hi - lo] = host.norm[lo:hi]
    order = np.argsort(host.term_hashes, kind="stable")
    return PackedDocShardedIndex(
        sorted_hash=np.broadcast_to(
            host.term_hashes[order][None, :], (S, W)).copy(),
        df_global=np.broadcast_to(
            host.df[order].astype(np.int32)[None, :], (S, W)).copy(),
        block_offsets=offs_a, packed=pk, tf_pairs=tp, block_bits=bits_a,
        block_base=base_a, block_count=cnt_a,
        tile_first=tf_arr, tile_count=tc_arr, norm=norm_a,
        doc_base=bounds[:-1].astype(np.int32), n_shards=S,
        num_docs=host.num_docs, dmax=dmax, tile=tile, block=block,
        words_per_block=wpb,
        max_blocks_per_term=max(ix.max_blocks_per_term for ix in shards),
        route_span_max=max(int(np.max(tc_arr[s])) if nbmax else 0
                           for s in range(S)),
        route_pairs_max=max(int(np.sum(tc_arr[s])) for s in range(S)),
    )


def build_doc_sharded_fused(host: PostingsHost, n_shards: int, *,
                            tile: int | None = None,
                            layout: str | None = None, policy=None):
    """Layout-ladder front door for the bulk doc-sharded fused engine:
    ``explicit layout > policy (size_model.LayoutCostModel over the
    host's aggregate stats) > historical "hor" default``.  Returns
    ``(index, reason)`` where index is a Blocked- or
    PackedDocShardedIndex — both accepted by
    ``make_doc_sharded_fused_scorer`` — and reason is the chooser's
    provenance string."""
    from repro.core import size_model
    stats = size_model.SegmentStats(
        num_docs=int(host.num_docs),
        num_postings=int(host.num_postings),
        num_terms=int(np.count_nonzero(np.asarray(host.df))))
    layout, reason = size_model.resolve_layout(layout, policy, stats,
                                               "hor")
    if layout == "packed":
        return build_doc_sharded_packed(host, n_shards, tile=tile), reason
    if layout == "hor":
        return build_doc_sharded_blocked(host, n_shards, tile=tile), reason
    if layout == "banded":
        raise ValueError(
            "banded is not a bulk doc-sharded layout: banded segments "
            "doc-shard through the segment-stack serving tier "
            "(stack_segment_shards / make_doc_sharded_segment_scorer), "
            "which carries both bands per group slot")
    raise ValueError(f"unknown layout: {layout!r}")


def make_doc_sharded_fused_scorer(
        index: BlockedDocShardedIndex | PackedDocShardedIndex,
        mesh: Mesh, axis: str, k: int = 10):
    """jit fn(query_hashes u32[T]) -> (scores[k], global doc ids[k]).

    Same contract as ``make_doc_sharded_scorer`` but every shard runs
    the fused decode-and-score Pallas kernel in CANDIDATE mode over its
    local posting blocks: each doc tile is reduced to a per-tile top-k
    in VMEM (the dense local score vector never reaches HBM), the
    shard's tile candidates become global candidates via ``doc_base``,
    and a thin all-gather candidate merge produces the global answer —
    the ODYS-style per-partition extraction + merge tier.

    Accepts either bulk layout: HOR blocks score in place, packed blocks
    decode IN VMEM (``fused_topk_packed_pallas``) — bit-identical
    answers, ~3x fewer posting bytes across HBM per shard."""
    from repro.distributed.topk import local_candidate_merge
    from repro.kernels import autotune
    from repro.kernels.fused_decode_score import (
        build_batched_pairs, default_k_tile, fused_topk_blocked_pallas,
        fused_topk_packed_pallas)
    from repro.kernels.ops import (expand_block_candidates,
                                    term_pairs_bound, warn_on_overflow)

    packed_layout = isinstance(index, PackedDocShardedIndex)
    arrs = index.device_arrays()
    dmax, tile = index.dmax, index.tile
    n_tiles = max(-(-dmax // tile), 1)
    num_docs = index.num_docs
    block = (index.block if packed_layout
             else int(index.block_docs.shape[-1]))
    m_blocks = max(index.max_blocks_per_term, 1)
    # tuned geometry for this shard size — the tile itself is pinned by
    # the sharded routing arrays, so only the routing-free axes (k_pad,
    # q_pad, reducer) follow the tuning table
    cfg = autotune.lookup("pallas", dmax,
                          "packed" if packed_layout else "hor")
    q_pad = cfg.q_pad
    if cfg.tile == tile:
        k_tile = cfg.resolve_k_tile(k)
    else:
        k_tile = min(default_k_tile(k, tile, cfg.k_pad), tile)

    names = ("sorted_hash", "df_global", "block_offsets", "tile_first",
             "tile_count", "norm", "doc_base")
    names += (("packed", "tf_pairs", "block_bits", "block_base",
               "block_count") if packed_layout
              else ("block_docs", "block_tfs"))
    sharded = {n: P(axis) for n in names}

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(sharded, P()), out_specs=(P(), P()), check_vma=False)
    def score(ix, qh):
        sq = {n: v[0] for n, v in ix.items()}    # drop shard dim
        qh = dedup_query_hashes(qh)
        t = qh.shape[0]
        pos = jnp.searchsorted(sq["sorted_hash"], qh).astype(jnp.int32)
        pos = jnp.clip(pos, 0, sq["sorted_hash"].shape[0] - 1)
        hit = (sq["sorted_hash"][pos] == qh) & (qh != 0)
        tid = jnp.where(hit, pos, -1)
        # idf uses GLOBAL df — scoring must match the single-node engine
        w = idf_fn(jnp.where(hit, sq["df_global"][pos], 0), num_docs)

        cand_block, cand_valid, cand_q, cand_w, _ = \
            expand_block_candidates(sq["block_offsets"], tid[None],
                                    w[None], m_blocks, block)
        max_pairs = max(min(index.route_pairs_max,
                            t * m_blocks * max(index.route_span_max, 1),
                            term_pairs_bound(t, m_blocks, n_tiles)), 8)
        pb, pt, pqw, pcap, ovf = build_batched_pairs(
            cand_block, cand_valid, cand_q, cand_w,
            sq["tile_first"], sq["tile_count"], n_tiles, 1, max_pairs)
        # budget above is exact, so this won't fire unless the budget
        # formula is ever loosened
        warn_on_overflow(ovf, "doc-sharded fused engine")
        pqw = jnp.pad(pqw, ((0, 0), (0, q_pad - 1)))
        qnorm = jnp.sqrt(jnp.maximum(jnp.sum(w * w), 1e-12))
        qn = jnp.full((q_pad,), 1.0, jnp.float32).at[0].set(qnorm)
        if packed_layout:
            vals, ids = fused_topk_packed_pallas(
                sq["packed"], sq["tf_pairs"], pb, pt, pqw, pcap,
                sq["block_bits"][pb], sq["block_base"][pb],
                sq["block_count"][pb], sq["norm"],
                jnp.zeros_like(sq["norm"]), qn, dmax, block, k_tile,
                tile=tile, reducer=cfg.reducer)
        else:
            vals, ids = fused_topk_blocked_pallas(
                sq["block_docs"], sq["block_tfs"], pb, pt, pqw, pcap,
                sq["norm"], jnp.zeros_like(sq["norm"]), qn, dmax, k_tile,
                tile=tile, reducer=cfg.reducer)
        gids = jnp.where(ids[0] >= 0, ids[0] + sq["doc_base"], -1)
        return local_candidate_merge(vals[0], gids, k, axis)

    return jax.jit(lambda qh: score(arrs, qh))


# ---------------------------------------------------------------------------
# document-partitioned segment stacks (the live index's serving tier)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StackGroupMeta:
    """Static signature of one ``(size_class, layout)`` group of sealed
    segments in a sharded stack.

    Sealing already quantizes every shape- and budget-bearing static to
    a geometric size class (``layouts.pad_blocked_to_class`` /
    ``pad_packed_to_class``); grouping the stack on the full tuple means
    two stacks whose segments fall into the same classes produce
    IDENTICAL jit signatures — the sharded twin of the live index's
    recompile-avoidance contract.  ``n_slots`` (the group's stack depth)
    is itself pow2-quantized so sealing one more same-class segment
    reuses the compiled scorer."""
    layout: str              # "hor" | "packed" | "banded"
    w_pad: int               # vocab slots per segment (size class)
    nb_pad: int              # posting-block rows per segment
    d_pad: int               # padded local doc span
    block: int
    words_per_block: int     # packed word lanes (0 for hor)
    n_slots: int             # G: per-shard stack depth (pow2, inert pads)
    max_blocks_per_term: int
    route_span_max: int
    route_pairs_max: int
    # banded only: the HOR band's statics ride alongside the packed
    # band's (which reuse the fields above); 0 for hor/packed groups so
    # pre-banded group keys are unchanged
    hor_nb_pad: int = 0
    hor_max_blocks_per_term: int = 0
    hor_route_span_max: int = 0
    hor_route_pairs_max: int = 0


def _segment_group_key(ix) -> StackGroupMeta:
    """The (size_class, layout) bucket a sealed segment stacks into.
    ``n_slots`` is filled in later (it is a property of the stack, not
    of one segment)."""
    if isinstance(ix, layouts.BandedCsrIndex):
        p, h = ix.packed, ix.hor
        return StackGroupMeta(
            layout="banded", w_pad=int(p.sorted_hash.shape[0]),
            nb_pad=int(p.packed.shape[0]), d_pad=int(p.docs.num_docs),
            block=p.block, words_per_block=p.words_per_block, n_slots=0,
            max_blocks_per_term=p.max_blocks_per_term,
            route_span_max=p.route_span_max,
            route_pairs_max=p.route_pairs_max,
            hor_nb_pad=int(h.block_docs.shape[0]),
            hor_max_blocks_per_term=h.max_blocks_per_term,
            hor_route_span_max=h.route_span_max,
            hor_route_pairs_max=h.route_pairs_max)
    if isinstance(ix, layouts.PackedCsrIndex):
        return StackGroupMeta(
            layout="packed", w_pad=int(ix.sorted_hash.shape[0]),
            nb_pad=int(ix.packed.shape[0]), d_pad=int(ix.docs.num_docs),
            block=ix.block, words_per_block=ix.words_per_block, n_slots=0,
            max_blocks_per_term=ix.max_blocks_per_term,
            route_span_max=ix.route_span_max,
            route_pairs_max=ix.route_pairs_max)
    if isinstance(ix, layouts.BlockedIndex):
        return StackGroupMeta(
            layout="hor", w_pad=int(ix.sorted_hash.shape[0]),
            nb_pad=int(ix.block_docs.shape[0]), d_pad=int(ix.docs.num_docs),
            block=ix.block, words_per_block=0, n_slots=0,
            max_blocks_per_term=ix.max_blocks_per_term,
            route_span_max=ix.route_span_max,
            route_pairs_max=ix.route_pairs_max)
    raise ValueError(f"unknown sealed-segment layout: {type(ix).__name__}")


def _group_array_names(layout: str) -> tuple:
    common = ("sorted_hash", "block_offsets", "tile_first", "tile_count",
              "norm", "doc_base")
    packed = ("packed", "tf_pairs", "block_bits", "block_base",
              "block_count")
    if layout == "banded":
        # the un-prefixed block arrays are the packed band's (the vocab
        # is shared — both bands carry the full hash-sorted vocabulary)
        return common + packed + ("hor_block_offsets", "hor_block_docs",
                                  "hor_block_tfs", "hor_tile_first",
                                  "hor_tile_count")
    if layout == "packed":
        return common + packed
    return common + ("block_docs", "block_tfs")


def _empty_group_arrays(meta: StackGroupMeta, n_shards: int) -> dict:
    """Inert [S, G, ...] arrays for one group: absent-hash vocab slots,
    tile_count 0 (never routed), and — for packed — bit width 1 with
    count 0, so padding slots are in-distribution for the decoder and
    contribute nothing."""
    S, G = n_shards, meta.n_slots
    w, nb, b = meta.w_pad, meta.nb_pad, meta.block
    arrays = {
        "sorted_hash": np.full((S, G, w), 0xFFFFFFFF, np.uint32),
        "block_offsets": np.zeros((S, G, w + 1), np.int32),
        "tile_first": np.zeros((S, G, nb), np.int32),
        "tile_count": np.zeros((S, G, nb), np.int32),
        "norm": np.zeros((S, G, meta.d_pad), np.float32),
        "doc_base": np.zeros((S, G), np.int32),
    }
    if meta.layout in ("packed", "banded"):
        arrays.update({
            "packed": np.zeros(
                (S, G, nb, layouts.lane_width(meta.words_per_block)),
                np.uint32),
            "tf_pairs": np.zeros((S, G, -(-nb // 2), b), np.uint32),
            "block_bits": np.ones((S, G, nb), np.int32),
            "block_base": np.zeros((S, G, nb), np.int32),
            "block_count": np.zeros((S, G, nb), np.int32),
        })
    else:
        arrays.update({
            "block_docs": np.full((S, G, nb, b), -1, np.int32),
            "block_tfs": np.zeros((S, G, nb, b), np.float32),
        })
    if meta.layout == "banded":
        hnb = meta.hor_nb_pad
        arrays.update({
            "hor_block_offsets": np.zeros((S, G, meta.w_pad + 1), np.int32),
            "hor_block_docs": np.full((S, G, hnb, b), -1, np.int32),
            "hor_block_tfs": np.zeros((S, G, hnb, b), np.float32),
            "hor_tile_first": np.zeros((S, G, hnb), np.int32),
            "hor_tile_count": np.zeros((S, G, hnb), np.int32),
        })
    return arrays


def _fill_group_slot(arrays: dict, s: int, g: int, seg) -> None:
    ix = seg.index
    if isinstance(ix, layouts.BandedCsrIndex):
        h = ix.hor
        arrays["hor_block_offsets"][s, g] = np.asarray(h.block_offsets)
        arrays["hor_block_docs"][s, g] = np.asarray(h.block_docs)
        arrays["hor_block_tfs"][s, g] = np.asarray(h.block_tfs)
        arrays["hor_tile_first"][s, g] = np.asarray(h.tile_first)
        arrays["hor_tile_count"][s, g] = np.asarray(h.tile_count)
        ix = ix.packed        # the un-prefixed arrays are the packed band
    arrays["sorted_hash"][s, g] = np.asarray(ix.sorted_hash)
    arrays["block_offsets"][s, g] = np.asarray(ix.block_offsets)
    arrays["tile_first"][s, g] = np.asarray(ix.tile_first)
    arrays["tile_count"][s, g] = np.asarray(ix.tile_count)
    arrays["norm"][s, g] = np.asarray(ix.docs.norm)
    arrays["doc_base"][s, g] = seg.doc_base
    if isinstance(ix, layouts.PackedCsrIndex):
        arrays["packed"][s, g] = np.asarray(ix.packed)
        arrays["tf_pairs"][s, g] = np.asarray(ix.tf_pairs)
        arrays["block_bits"][s, g] = np.asarray(ix.block_bits)
        arrays["block_base"][s, g] = np.asarray(ix.block_base)
        arrays["block_count"][s, g] = np.asarray(ix.block_count)
    else:
        arrays["block_docs"][s, g] = np.asarray(ix.block_docs)
        arrays["block_tfs"][s, g] = np.asarray(ix.block_tfs)


@dataclasses.dataclass
class SegmentStackShards:
    """Per-shard stacks of sealed live-index segments, grouped by
    ``(size_class, layout)`` and stacked ``[S, G, ...]`` per group
    (G = the group's deepest per-shard stack, pow2-padded; empty slots
    inert).  Each shard owns WHOLE segments — the ODYS-style partition-
    by-run layout — so a query runs one fused candidate kernel per local
    segment and the global answer is a candidate merge, exactly the
    single-node live path with shards playing the role of stacks.  HOR
    and delta+bit-packed sealed segments mix freely: each group carries
    its own layout and the candidate lists are canonicalized (ascending
    doc id) before the merge, so ties still break on lowest global id."""
    groups: list               # [(StackGroupMeta, {name: np [S, G, ...]})]
    vocab_hash: np.ndarray     # u32[Wp] unified, hash-sorted (replicated)
    vocab_df: np.ndarray       # i32[Wp] LIVE global df (replicated)
    n_shards: int
    live_docs: int             # D behind idf (host query weights)
    tile: int

    def signature(self) -> tuple:
        """Hashable static structure: the jit-cache key component."""
        return tuple(meta for meta, _ in self.groups)

    def device_arrays(self) -> dict:
        return {"groups": [{n: jnp.asarray(v) for n, v in arrays.items()}
                           for _, arrays in self.groups]}

    def query_weights(self, qh):
        """(dedup'd hashes u32[T], idf f32[T], qnorm f32) of one query
        from the replicated live vocabulary stats — the single-node live
        index's host computation (``live_index.query_weights``)."""
        from repro.core.live_index import _dedup_np, query_weights
        qh = _dedup_np(np.asarray(qh, np.uint32))
        pos = np.clip(np.searchsorted(self.vocab_hash, qh), 0,
                      len(self.vocab_hash) - 1)
        hit = (self.vocab_hash[pos] == qh) & (qh != 0)
        w, qnorm = query_weights(np.where(hit, self.vocab_df[pos], 0),
                                 self.live_docs)
        return qh, w, qnorm


def _refuse_saturating(ranking) -> None:
    """The sharded engines inline tf-idf cosine's tail; BM25 is served
    by the single-chip path only."""
    if ranking.saturates:
        raise ValueError(f"the sharded engines score tf-idf cosine only, "
                         f"not {ranking.name}")


def stack_segment_shards(live_index, n_shards: int) -> SegmentStackShards:
    """Distribute a SegmentedIndex's sealed stack across ``n_shards``.
    The delta must be sealed first — the serving tier replicates
    immutable runs only.

    Also accepts an epoch-pinned ``LiveView`` (``SegmentedIndex.view()``
    / ``serve.snapshot.pin``): the sharded serving tier then snapshots a
    CONSISTENT epoch — build the stacks from a pin while ingest keeps
    landing, and the sharded scorer answers exactly as the single-node
    pinned view does, no quiesce needed.  Sealed segments may be HOR
    blocks (``seal_layout="hor"``), delta+bit-packed blocks
    (``"packed"``), or any per-seal mixture: segments stack into
    per-``(size_class, layout)`` groups, so a warm
    ``make_doc_sharded_segment_scorer`` jit cache sees zero new entries
    when a rebuilt stack hits the same group signatures.  tf-idf cosine
    only: a BM25 index is refused."""
    from repro.core.live_index import LiveView
    _refuse_saturating(live_index.ranking)
    if isinstance(live_index, LiveView):
        if live_index.delta_n_docs:
            raise ValueError("pin a view with a sealed delta before "
                             "sharding the stack")
        segs = list(live_index.segments)
        vocab_hashes = live_index.hashes
        vocab_df = np.asarray(live_index.df)
        live_docs = live_index.live_docs
    else:
        if live_index.delta_postings or live_index._delta.n_docs:
            raise ValueError("seal() the delta before sharding the stack")
        segs = live_index.segments()
        vocab_hashes = live_index.term_hashes
        vocab_df = np.asarray(live_index._df)
        live_docs = live_index.live_doc_count
    if not segs:
        raise ValueError("no sealed segments to shard")
    tiles = {s.index.route_tile for s in segs}
    if len(tiles) != 1:
        raise ValueError(f"segments disagree on route_tile: {tiles}")
    # contiguous runs per shard (NOT round-robin): the all-gather
    # candidate merge concatenates shard 0's candidates first, so shards
    # must cover ascending doc-id ranges for exact score ties to break
    # on lowest global doc id, like the single-node live index
    splits = np.array_split(np.arange(len(segs)), n_shards)
    shards = [[segs[i] for i in idx] for idx in splits]

    # bucket by (size_class, layout); G = pow2-padded deepest stack
    keys = sorted({_segment_group_key(s.index) for s in segs},
                  key=lambda m: dataclasses.astuple(m))
    groups = []
    for key in keys:
        depth = max(sum(1 for s in stack
                        if _segment_group_key(s.index) == key)
                    for stack in shards)
        meta = dataclasses.replace(
            key, n_slots=layouts.size_class(depth, base=1))
        arrays = _empty_group_arrays(meta, n_shards)
        for s, stack in enumerate(shards):
            g = 0
            for seg in stack:
                if _segment_group_key(seg.index) == key:
                    _fill_group_slot(arrays, s, g, seg)
                    g += 1
        groups.append((meta, arrays))

    order = np.argsort(vocab_hashes, kind="stable")
    w = len(vocab_hashes)
    w_pad = layouts.size_class(max(w, 1), base=256)
    vh = np.full(w_pad, 0xFFFFFFFF, np.uint32)
    vh[:w] = vocab_hashes[order].astype(np.uint32)
    vdf = np.zeros(w_pad, np.int32)
    vdf[:w] = vocab_df[order].astype(np.int32)
    return SegmentStackShards(
        groups=groups, vocab_hash=vh, vocab_df=vdf, n_shards=n_shards,
        live_docs=live_docs, tile=segs[0].index.route_tile)


# compiled stack scorers, keyed on (mesh, axis, k, static stack
# signature): rebuilding the stack at a new epoch with the same
# (size_class, layout) group structure reuses the warm executable
_STACK_SCORER_CACHE: dict = {}


def stack_scorer_cache_sizes() -> dict:
    """jit-cache counters for the sharded segment-stack scorer — the
    sharded twin of ``live_index.scorer_cache_sizes`` (tests assert zero
    growth across same-class stack rebuilds)."""
    return {
        "doc_sharded_segment_scorers": len(_STACK_SCORER_CACHE),
        "doc_sharded_segment_entries":
            sum(f._cache_size() for f in _STACK_SCORER_CACHE.values()),
    }


def _build_stack_scorer(mesh: Mesh, axis: str, k: int, tile: int,
                        metas: tuple, cfgs: tuple = ()):
    from repro.distributed.topk import (canonicalize_candidates,
                                        local_candidate_merge)
    from repro.kernels import autotune
    from repro.kernels.fused_decode_score import (
        build_batched_pairs, default_k_tile, extract_tile_candidates,
        fused_score_blocked_pallas, fused_score_packed_pallas,
        fused_topk_blocked_pallas, fused_topk_packed_pallas)
    from repro.kernels.ops import expand_block_candidates, term_pairs_bound

    if not cfgs:
        cfgs = tuple(autotune.lookup("pallas", m.d_pad, m.layout)
                     for m in metas)

    def _group_k_tile(cfg):
        # the stack tile is pinned by the sharded routing arrays; only
        # apply the tuned k_tile when the table agrees on the tile, else
        # fall back to the tuned k_pad quantum at the stack tile
        if cfg.tile == tile:
            return cfg.resolve_k_tile(k)
        return min(default_k_tile(k, tile, cfg.k_pad), tile)
    group_specs = [{n: P(axis) for n in _group_array_names(m.layout)}
                   for m in metas]
    in_specs = ({"groups": group_specs}, P(), P(), P())

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=in_specs, out_specs=(P(), P()),
        check_vma=False)
    def score(ix, qh, w, qnorm):
        # qh is dedup'd and (w, qnorm) are the global idf weights of the
        # live vocabulary, computed on the host exactly as the
        # single-node live index computes them (live_index.query_weights)
        t = qh.shape[0]
        all_v, all_i = [], []
        for meta, cfg, g_arrs in zip(metas, cfgs, ix["groups"]):
            sq = {n: v[0] for n, v in g_arrs.items()}   # drop shard dim
            n_tiles = max(-(-meta.d_pad // tile), 1)
            m_blocks = max(meta.max_blocks_per_term, 1)
            k_tile = _group_k_tile(cfg)
            if meta.layout == "banded":
                # per-band dense partials summed BEFORE extraction — a
                # per-band candidate top-k cannot merge (scores are
                # additive over terms), so the banded slot mirrors the
                # single-host banded engine: one lookup, two fused dense
                # launches, shared scoring tail, per-tile candidates
                m_h = max(meta.hor_max_blocks_per_term, 1)
                mp_p = max(min(meta.route_pairs_max,
                               t * m_blocks * max(meta.route_span_max, 1),
                               term_pairs_bound(t, m_blocks, n_tiles)), 8)
                mp_h = max(min(meta.hor_route_pairs_max,
                               t * m_h * max(meta.hor_route_span_max, 1),
                               term_pairs_bound(t, m_h, n_tiles)), 8)
                for g in range(meta.n_slots):
                    pos = jnp.searchsorted(sq["sorted_hash"][g],
                                           qh).astype(jnp.int32)
                    pos = jnp.clip(pos, 0, sq["sorted_hash"].shape[1] - 1)
                    hit = (sq["sorted_hash"][g][pos] == qh) & (qh != 0)
                    tid = jnp.where(hit, pos, -1)
                    cb, cv, cq, cw, _ = expand_block_candidates(
                        sq["block_offsets"][g], tid[None], w[None],
                        m_blocks, meta.block)
                    pb, pt, pqw, pcap, _ovf = build_batched_pairs(
                        cb, cv, cq, cw, sq["tile_first"][g],
                        sq["tile_count"][g], n_tiles, 1, mp_p)
                    pqw = jnp.pad(pqw, ((0, 0), (0, cfg.q_pad - 1)))
                    acc = fused_score_packed_pallas(
                        sq["packed"][g], sq["tf_pairs"][g], pb, pt, pqw,
                        pcap, sq["block_bits"][g][pb],
                        sq["block_base"][g][pb], sq["block_count"][g][pb],
                        meta.d_pad, meta.block, tile)[0]
                    cb, cv, cq, cw, _ = expand_block_candidates(
                        sq["hor_block_offsets"][g], tid[None], w[None],
                        m_h, meta.block)
                    pb, pt, pqw, pcap, _ovf = build_batched_pairs(
                        cb, cv, cq, cw, sq["hor_tile_first"][g],
                        sq["hor_tile_count"][g], n_tiles, 1, mp_h)
                    pqw = jnp.pad(pqw, ((0, 0), (0, cfg.q_pad - 1)))
                    acc = acc + fused_score_blocked_pallas(
                        sq["hor_block_docs"][g], sq["hor_block_tfs"][g],
                        pb, pt, pqw, pcap, meta.d_pad, tile)[0]
                    nrm = sq["norm"][g]
                    final = jnp.where(
                        (nrm > 0) & (acc > 0),
                        acc / (jnp.maximum(nrm, 1e-12) * qnorm), -jnp.inf)
                    vals, ids = extract_tile_candidates(final[None], tile,
                                                        k_tile)
                    all_v.append(vals[0])
                    all_i.append(jnp.where(ids[0] >= 0,
                                           ids[0] + sq["doc_base"][g], -1))
                continue
            qn = jnp.full((cfg.q_pad,), 1.0, jnp.float32).at[0].set(qnorm)
            max_pairs = max(min(meta.route_pairs_max,
                                t * m_blocks * max(meta.route_span_max, 1),
                                term_pairs_bound(t, m_blocks, n_tiles)), 8)
            for g in range(meta.n_slots):             # static stack depth
                pos = jnp.searchsorted(sq["sorted_hash"][g],
                                       qh).astype(jnp.int32)
                pos = jnp.clip(pos, 0, sq["sorted_hash"].shape[1] - 1)
                hit = (sq["sorted_hash"][g][pos] == qh) & (qh != 0)
                tid = jnp.where(hit, pos, -1)
                cand_block, cand_valid, cand_q, cand_w, _ = \
                    expand_block_candidates(sq["block_offsets"][g],
                                            tid[None], w[None], m_blocks,
                                            meta.block)
                pb, pt, pqw, pcap, _ovf = build_batched_pairs(
                    cand_block, cand_valid, cand_q, cand_w,
                    sq["tile_first"][g], sq["tile_count"][g], n_tiles, 1,
                    max_pairs)
                pqw = jnp.pad(pqw, ((0, 0), (0, cfg.q_pad - 1)))
                if meta.layout == "packed":
                    vals, ids = fused_topk_packed_pallas(
                        sq["packed"][g], sq["tf_pairs"][g], pb, pt, pqw,
                        pcap, sq["block_bits"][g][pb],
                        sq["block_base"][g][pb], sq["block_count"][g][pb],
                        sq["norm"][g], jnp.zeros_like(sq["norm"][g]), qn,
                        meta.d_pad, meta.block, k_tile, tile=tile,
                        reducer=cfg.reducer)
                else:
                    vals, ids = fused_topk_blocked_pallas(
                        sq["block_docs"][g], sq["block_tfs"][g], pb, pt,
                        pqw, pcap, sq["norm"][g],
                        jnp.zeros_like(sq["norm"][g]), qn, meta.d_pad,
                        k_tile, tile=tile, reducer=cfg.reducer)
                all_v.append(vals[0])
                all_i.append(jnp.where(ids[0] >= 0,
                                       ids[0] + sq["doc_base"][g], -1))
        # group-major concatenation interleaves doc ranges (mixed
        # layouts, multiple classes) — canonicalize so the merge
        # tie-breaks on lowest global doc id regardless of group order
        cv, ci = canonicalize_candidates(jnp.concatenate(all_v),
                                         jnp.concatenate(all_i))
        return local_candidate_merge(cv, ci, k, axis)

    return jax.jit(score)


def make_doc_sharded_segment_scorer(index: SegmentStackShards, mesh: Mesh,
                                    axis: str, k: int = 10):
    """jit fn(query_hashes u32[T]) -> (scores[k], global doc ids[k]).

    Every shard walks its local segment stack — one fused candidate
    kernel per segment, HOR blocks read in place, packed blocks decoded
    IN VMEM (idf from the replicated LIVE global df, so a shard scores
    exactly as the single-node live index does) — shifts tile candidates
    to global ids via the per-segment doc_base, and the usual all-gather
    candidate merge yields the global top-k.  Deleted docs ride in as
    norm == 0 per segment — tombstones work unchanged at cluster scale.

    The compiled program is cached on (mesh, axis, k, stack signature):
    a stack rebuilt at a newer epoch whose segments fall into the same
    ``(size_class, layout)`` groups reuses the warm executable — zero
    new jit entries (``stack_scorer_cache_sizes``)."""
    if mesh.shape[axis] != index.n_shards:
        raise ValueError(
            f"stack was built for {index.n_shards} shards but mesh axis "
            f"{axis!r} has {mesh.shape[axis]} devices — shard_map would "
            f"silently drop whole per-shard stacks")
    from repro.kernels import autotune
    metas = index.signature()
    # the active tuning table is part of the compiled program — key the
    # cache on the resolved per-group configs so swapping tables (or an
    # empty table, which resolves to historical defaults) never serves a
    # stale geometry
    cfgs = tuple(autotune.lookup("pallas", m.d_pad, m.layout)
                 for m in metas)
    key = (mesh, axis, k, index.tile, index.n_shards, metas, cfgs)
    fn = _STACK_SCORER_CACHE.get(key)
    if fn is None:
        fn = _build_stack_scorer(mesh, axis, k, index.tile, metas, cfgs)
        _STACK_SCORER_CACHE[key] = fn
    arrs = index.device_arrays()

    def scorer(qh, trace=None):
        # trace=None is the hot path: no span objects, no extra sync —
        # the caller blocks on the results whenever it reads them
        qh, w, qnorm = index.query_weights(qh)
        if trace is None:
            return fn(arrs, qh, w, qnorm)
        span = trace.span(
            "shard_fanout", parent="score", n_shards=index.n_shards,
            k=k, groups=[{"size_class": m.d_pad, "layout": m.layout}
                         for m in metas])
        out = fn(arrs, qh, w, qnorm)
        span.end()
        sync = trace.span("shard_sync", parent="score")
        out = jax.block_until_ready(out)
        sync.end()
        return out

    return scorer


# ---------------------------------------------------------------------------
# term-partitioned, fused Pallas engine (HOR blocks per vocab shard)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BlockedTermShardedIndex:
    """Stacked per-vocab-shard HOR arrays for the fused engine.

    Each shard owns a contiguous hash range of the vocabulary as whole
    posting lists re-packed into 128-lane blocks with GLOBAL doc ids
    (the doc/tile space is the full corpus, identical on every shard),
    plus the build-time (block -> doc-tile) routing cache.
    """
    sorted_hash: np.ndarray    # u32[S, Wmax]  (padded with 0xFFFFFFFF)
    df: np.ndarray             # i32[S, Wmax]  global df (terms are whole)
    block_offsets: np.ndarray  # i32[S, Wmax+1]
    block_docs: np.ndarray     # i32[S, NBmax, BLOCK]  GLOBAL doc ids
    block_tfs: np.ndarray      # f32[S, NBmax, BLOCK]
    tile_first: np.ndarray     # i32[S, NBmax]
    tile_count: np.ndarray     # i32[S, NBmax]
    norm: np.ndarray           # f32[D] (replicated)
    n_shards: int
    num_docs: int
    tile: int
    max_blocks_per_term: int
    route_span_max: int
    route_pairs_max: int

    def device_arrays(self) -> dict:
        return {f.name: jnp.asarray(getattr(self, f.name))
                for f in dataclasses.fields(self)
                if isinstance(getattr(self, f.name), np.ndarray)}


def build_term_sharded_blocked(host: PostingsHost, n_shards: int
                               ) -> BlockedTermShardedIndex:
    subs, wmax = _term_shard_subhosts(host, n_shards)
    shards = [layouts.build_blocked(sub) for sub in subs]
    block = shards[0].block
    nbmax = max(int(ix.block_docs.shape[0]) for ix in shards)
    S = n_shards
    sh_a = np.full((S, wmax), 0xFFFFFFFF, np.uint32)
    df_a = np.zeros((S, wmax), np.int32)
    offs_a = np.zeros((S, wmax + 1), np.int32)
    bd = np.full((S, nbmax, block), -1, np.int32)
    bt = np.zeros((S, nbmax, block), np.float32)
    tf_a = np.zeros((S, nbmax), np.int32)
    tc_a = np.zeros((S, nbmax), np.int32)
    for s, ix in enumerate(shards):
        w = int(ix.sorted_hash.shape[0])
        nb = int(ix.block_docs.shape[0])
        sh_a[s, :w] = np.asarray(ix.sorted_hash)
        df_a[s, :w] = np.asarray(ix.df)
        offs_a[s, :w + 1] = np.asarray(ix.block_offsets)
        offs_a[s, w + 1:] = offs_a[s, w]
        bd[s, :nb] = np.asarray(ix.block_docs)
        bt[s, :nb] = np.asarray(ix.block_tfs)
        tf_a[s, :nb] = np.asarray(ix.tile_first)
        tc_a[s, :nb] = np.asarray(ix.tile_count)
    return BlockedTermShardedIndex(
        sorted_hash=sh_a, df=df_a, block_offsets=offs_a,
        block_docs=bd, block_tfs=bt, tile_first=tf_a, tile_count=tc_a,
        norm=host.norm.astype(np.float32), n_shards=S,
        num_docs=host.num_docs, tile=layouts.ROUTE_TILE,
        max_blocks_per_term=max(ix.max_blocks_per_term for ix in shards),
        route_span_max=max(ix.route_span_max for ix in shards),
        route_pairs_max=max(ix.route_pairs_max for ix in shards),
    )


@dataclasses.dataclass
class PackedTermShardedIndex:
    """Stacked per-vocab-shard delta+bit-packed arrays for the fused
    engine — the compressed twin of ``BlockedTermShardedIndex``.

    Each shard owns a contiguous hash range of the vocabulary as whole
    posting lists, re-compressed per shard: doc-id deltas bit-packed at
    a per-block width (GLOBAL doc ids, so the doc/tile space is the full
    corpus and identical on every shard), f16 tfs in u32 pair rows, plus
    the per-block decode scalars and the build-time (block -> doc-tile)
    routing cache.
    The fused kernel decodes blocks IN VMEM, so the compressed words are
    the only posting bytes a query moves across HBM per shard.
    """
    sorted_hash: np.ndarray    # u32[S, Wmax]  (padded with 0xFFFFFFFF)
    df: np.ndarray             # i32[S, Wmax]  global df (terms are whole)
    block_offsets: np.ndarray  # i32[S, Wmax+1]
    packed: np.ndarray         # u32[S, NBmax, lanes]  bit-packed deltas
    tf_pairs: np.ndarray       # u32[S, ceil(NBmax/2), BLOCK]
    block_bits: np.ndarray     # i32[S, NBmax]  (1 on padding blocks)
    block_base: np.ndarray     # i32[S, NBmax]
    block_count: np.ndarray    # i32[S, NBmax]  (0 on padding blocks)
    tile_first: np.ndarray     # i32[S, NBmax]
    tile_count: np.ndarray     # i32[S, NBmax]
    norm: np.ndarray           # f32[D] (replicated)
    n_shards: int
    num_docs: int
    tile: int
    block: int
    words_per_block: int
    max_blocks_per_term: int
    route_span_max: int
    route_pairs_max: int

    def device_arrays(self) -> dict:
        return {f.name: jnp.asarray(getattr(self, f.name))
                for f in dataclasses.fields(self)
                if isinstance(getattr(self, f.name), np.ndarray)}


def _term_shard_subhosts(host: PostingsHost, n_shards: int):
    """Slice the global posting lists into per-vocab-shard PostingsHost
    sub-indexes (contiguous hash ranges, whole lists, GLOBAL doc ids) —
    the one slicing both term-sharded builders share, so the HOR and
    packed structures see identical per-shard term order and block
    boundaries (that is what makes the two engines bit-identical)."""
    order = np.argsort(host.term_hashes, kind="stable")
    W = host.num_terms
    bounds = np.linspace(0, W, n_shards + 1).astype(np.int64)
    subs = []
    for s in range(n_shards):
        terms = order[bounds[s]:bounds[s + 1]]
        lens = (host.offsets[terms + 1] - host.offsets[terms]).astype(np.int64)
        offs = np.zeros(len(terms) + 1, dtype=np.int64)
        np.cumsum(lens, out=offs[1:])
        docs = np.zeros(int(offs[-1]), np.int32)
        tfs = np.zeros(int(offs[-1]), np.float32)
        for i, t in enumerate(terms):
            a, bnd = host.offsets[t], host.offsets[t + 1]
            docs[offs[i]:offs[i + 1]] = host.doc_ids[a:bnd]
            tfs[offs[i]:offs[i + 1]] = host.tfs[a:bnd]
        subs.append(PostingsHost(term_hashes=host.term_hashes[terms],
                                 df=host.df[terms].astype(np.int32),
                                 offsets=offs, doc_ids=docs, tfs=tfs,
                                 num_docs=host.num_docs,
                                 norm=host.norm, rank=host.rank))
    wmax = int(np.max(np.diff(bounds)))
    return subs, wmax


def build_term_sharded_packed(host: PostingsHost, n_shards: int
                              ) -> PackedTermShardedIndex:
    """Per-vocab-shard re-compression: slice the global posting lists
    per hash range, then delta+bit-pack each shard's lists (global doc
    ids, per-block minimal widths) — so the term-partitioned read path
    streams compressed bytes only, like the single-node packed engine."""
    subs, wmax = _term_shard_subhosts(host, n_shards)
    shards = [layouts.build_packed_csr(sub) for sub in subs]
    block = shards[0].block
    nbmax = max(int(ix.packed.shape[0]) for ix in shards)
    wpb = max(ix.words_per_block for ix in shards)
    S = n_shards
    sh_a = np.full((S, wmax), 0xFFFFFFFF, np.uint32)
    df_a = np.zeros((S, wmax), np.int32)
    offs_a = np.zeros((S, wmax + 1), np.int32)
    pk = np.zeros((S, nbmax, layouts.lane_width(wpb)), np.uint32)
    tp = np.zeros((S, -(-nbmax // 2), block), np.uint32)
    bits_a = np.ones((S, nbmax), np.int32)     # padding blocks decode inert
    base_a = np.zeros((S, nbmax), np.int32)
    cnt_a = np.zeros((S, nbmax), np.int32)
    tf_a = np.zeros((S, nbmax), np.int32)
    tc_a = np.zeros((S, nbmax), np.int32)
    for s, ix in enumerate(shards):
        w = int(ix.sorted_hash.shape[0])
        nb = int(ix.packed.shape[0])
        sh_a[s, :w] = np.asarray(ix.sorted_hash)
        df_a[s, :w] = np.asarray(ix.df)
        offs_a[s, :w + 1] = np.asarray(ix.block_offsets)
        offs_a[s, w + 1:] = offs_a[s, w]
        pk[s, :nb, :ix.packed.shape[1]] = np.asarray(ix.packed)
        tp[s, :ix.tf_pairs.shape[0]] = np.asarray(ix.tf_pairs)
        bits_a[s, :nb] = np.asarray(ix.block_bits)
        base_a[s, :nb] = np.asarray(ix.block_base)
        cnt_a[s, :nb] = np.asarray(ix.block_count)
        tf_a[s, :nb] = np.asarray(ix.tile_first)
        tc_a[s, :nb] = np.asarray(ix.tile_count)
    return PackedTermShardedIndex(
        sorted_hash=sh_a, df=df_a, block_offsets=offs_a, packed=pk,
        tf_pairs=tp, block_bits=bits_a, block_base=base_a,
        block_count=cnt_a, tile_first=tf_a, tile_count=tc_a,
        norm=host.norm.astype(np.float32), n_shards=S,
        num_docs=host.num_docs, tile=layouts.ROUTE_TILE, block=block,
        words_per_block=wpb,
        max_blocks_per_term=max(ix.max_blocks_per_term for ix in shards),
        route_span_max=max(ix.route_span_max for ix in shards),
        route_pairs_max=max(ix.route_pairs_max for ix in shards),
    )


@dataclasses.dataclass
class BandedTermShardedIndex:
    """Stacked per-vocab-shard BANDED arrays for the fused engine.

    Each shard re-bands its hash range with the byte model
    (``layouts.build_banded``): high-df terms pack into that shard's
    packed band at a band-local word stride, the decode-bound tail
    stays HOR.  Terms are whole, so every query term's postings live
    entirely in ONE band of one shard — the scorer sums the two dense
    band partials locally BEFORE the cross-shard psum, keeping the
    term-sharding tax at one [D] reduction exactly like the
    single-layout twins.  The un-prefixed block arrays are the packed
    band's; the HOR band rides under ``hor_*``.
    """
    sorted_hash: np.ndarray        # u32[S, Wmax]  (padded with 0xFFFFFFFF)
    df: np.ndarray                 # i32[S, Wmax]  global df (whole terms)
    block_offsets: np.ndarray      # i32[S, Wmax+1]   packed band
    packed: np.ndarray             # u32[S, NBmax, lanes]
    tf_pairs: np.ndarray           # u32[S, ceil(NBmax/2), BLOCK]
    block_bits: np.ndarray         # i32[S, NBmax]  (1 on padding blocks)
    block_base: np.ndarray         # i32[S, NBmax]
    block_count: np.ndarray        # i32[S, NBmax]  (0 on padding blocks)
    tile_first: np.ndarray         # i32[S, NBmax]
    tile_count: np.ndarray         # i32[S, NBmax]
    hor_block_offsets: np.ndarray  # i32[S, Wmax+1]   hor band
    hor_block_docs: np.ndarray     # i32[S, HNBmax, BLOCK]
    hor_block_tfs: np.ndarray      # f32[S, HNBmax, BLOCK]
    hor_tile_first: np.ndarray     # i32[S, HNBmax]
    hor_tile_count: np.ndarray     # i32[S, HNBmax]
    norm: np.ndarray               # f32[D] (replicated)
    n_shards: int
    num_docs: int
    tile: int
    block: int
    words_per_block: int
    max_blocks_per_term: int
    route_span_max: int
    route_pairs_max: int
    hor_max_blocks_per_term: int
    hor_route_span_max: int
    hor_route_pairs_max: int

    def device_arrays(self) -> dict:
        return {f.name: jnp.asarray(getattr(self, f.name))
                for f in dataclasses.fields(self)
                if isinstance(getattr(self, f.name), np.ndarray)}


def build_term_sharded_banded(host: PostingsHost, n_shards: int
                              ) -> BandedTermShardedIndex:
    """Per-vocab-shard banding over the SAME slicing as the hor/packed
    term-sharded builders — identical per-shard term order, so a query
    term resolves to the same shard regardless of layout."""
    subs, wmax = _term_shard_subhosts(host, n_shards)
    shards = [layouts.build_banded(sub) for sub in subs]
    block = shards[0].block
    nbmax = max(int(ix.packed.packed.shape[0]) for ix in shards)
    hnbmax = max(int(ix.hor.block_docs.shape[0]) for ix in shards)
    wpb = max(ix.packed.words_per_block for ix in shards)
    S = n_shards
    sh_a = np.full((S, wmax), 0xFFFFFFFF, np.uint32)
    df_a = np.zeros((S, wmax), np.int32)
    offs_a = np.zeros((S, wmax + 1), np.int32)
    pk = np.zeros((S, nbmax, layouts.lane_width(wpb)), np.uint32)
    tp = np.zeros((S, -(-nbmax // 2), block), np.uint32)
    bits_a = np.ones((S, nbmax), np.int32)     # padding blocks decode inert
    base_a = np.zeros((S, nbmax), np.int32)
    cnt_a = np.zeros((S, nbmax), np.int32)
    tf_a = np.zeros((S, nbmax), np.int32)
    tc_a = np.zeros((S, nbmax), np.int32)
    h_offs_a = np.zeros((S, wmax + 1), np.int32)
    h_bd = np.full((S, hnbmax, block), -1, np.int32)
    h_bt = np.zeros((S, hnbmax, block), np.float32)
    h_tf_a = np.zeros((S, hnbmax), np.int32)
    h_tc_a = np.zeros((S, hnbmax), np.int32)
    for s, ix in enumerate(shards):
        p, h = ix.packed, ix.hor
        w = int(p.sorted_hash.shape[0])
        nb = int(p.packed.shape[0])
        hnb = int(h.block_docs.shape[0])
        sh_a[s, :w] = np.asarray(p.sorted_hash)
        df_a[s, :w] = np.asarray(ix.df)
        offs_a[s, :w + 1] = np.asarray(p.block_offsets)
        offs_a[s, w + 1:] = offs_a[s, w]
        pk[s, :nb, :p.packed.shape[1]] = np.asarray(p.packed)
        tp[s, :p.tf_pairs.shape[0]] = np.asarray(p.tf_pairs)
        bits_a[s, :nb] = np.asarray(p.block_bits)
        base_a[s, :nb] = np.asarray(p.block_base)
        cnt_a[s, :nb] = np.asarray(p.block_count)
        tf_a[s, :nb] = np.asarray(p.tile_first)
        tc_a[s, :nb] = np.asarray(p.tile_count)
        h_offs_a[s, :w + 1] = np.asarray(h.block_offsets)
        h_offs_a[s, w + 1:] = h_offs_a[s, w]
        h_bd[s, :hnb] = np.asarray(h.block_docs)
        h_bt[s, :hnb] = np.asarray(h.block_tfs)
        h_tf_a[s, :hnb] = np.asarray(h.tile_first)
        h_tc_a[s, :hnb] = np.asarray(h.tile_count)
    return BandedTermShardedIndex(
        sorted_hash=sh_a, df=df_a, block_offsets=offs_a, packed=pk,
        tf_pairs=tp, block_bits=bits_a, block_base=base_a,
        block_count=cnt_a, tile_first=tf_a, tile_count=tc_a,
        hor_block_offsets=h_offs_a, hor_block_docs=h_bd,
        hor_block_tfs=h_bt, hor_tile_first=h_tf_a, hor_tile_count=h_tc_a,
        norm=host.norm.astype(np.float32), n_shards=S,
        num_docs=host.num_docs, tile=layouts.ROUTE_TILE, block=block,
        words_per_block=wpb,
        max_blocks_per_term=max(ix.packed.max_blocks_per_term
                                for ix in shards),
        route_span_max=max(ix.packed.route_span_max for ix in shards),
        route_pairs_max=max(ix.packed.route_pairs_max for ix in shards),
        hor_max_blocks_per_term=max(ix.hor.max_blocks_per_term
                                    for ix in shards),
        hor_route_span_max=max(ix.hor.route_span_max for ix in shards),
        hor_route_pairs_max=max(ix.hor.route_pairs_max for ix in shards),
    )


def build_term_sharded_from_view(view, n_shards: int,
                                 layout: str = "hor"):
    """Term-partition an epoch-pinned ``LiveView``: bulk-build the
    view's live corpus and shard the vocabulary.

    Returns ``(index, live_ids)`` — the fused term-sharded index over
    the COMPACT live-doc space plus the ascending global ids that map
    compact results back (ascending, so exact-score ties still break on
    lowest global doc id after the mapping).  This is the serving
    tier's alternate topology: unlike the segment-stack path it
    re-builds (and re-compiles for new shapes) per epoch, which is the
    right trade only when the corpus is near-static between handoffs.
    tf-idf cosine only: a BM25 view is refused.
    """
    from repro.core import build
    _refuse_saturating(view.ranking)
    tc_live, live_ids = view.export_live_corpus()
    builder = {"packed": build_term_sharded_packed,
               "banded": build_term_sharded_banded}.get(
                   layout, build_term_sharded_blocked)
    host = build.bulk_build(tc_live)
    return builder(host, n_shards), np.asarray(live_ids, np.int64)


def make_term_sharded_fused_scorer(
        index: (BlockedTermShardedIndex | PackedTermShardedIndex
                | BandedTermShardedIndex),
        mesh: Mesh, axis: str, k: int = 10, cap: int | None = None,
        return_stats: bool = False):
    """jit fn(query_hashes u32[T]) -> (scores[k], global doc ids[k]).

    Term-partitioned fused engine: each shard scores only the query
    terms it owns through the fused Pallas kernel (partial scores over
    the GLOBAL doc space; HOR blocks read in place, packed blocks
    decoded IN VMEM so only compressed bytes cross HBM), pays the
    term-sharding tax — a full [D] psum of partials — then the candidate
    tier takes over: every shard reduces its 1/S slice of the doc-tile
    grid to per-tile candidates and an all-gather candidate merge yields
    the global top-k, so the post-psum ranking tail is candidate-sized
    instead of dense.

    ``cap`` bounds postings read per term at posting granularity (the
    oracle's gather cap); with ``return_stats=True`` the scorer returns
    ``((scores, ids), stats)`` where ``stats["truncated_terms"]`` counts
    query terms whose posting list exceeded ``cap`` — AGGREGATED across
    shards with a psum, the same way the multi-segment conjunctive sums
    its per-segment truncation counters, so truncation on ANY shard is
    surfaced."""
    from repro.distributed.topk import local_candidate_merge
    from repro.kernels import autotune
    from repro.kernels.fused_decode_score import (
        build_batched_pairs, default_k_tile,
        extract_tile_candidates, fused_score_blocked_pallas,
        fused_score_packed_pallas)
    from repro.kernels.ops import (expand_block_candidates,
                                    record_truncated, warn_on_overflow)

    packed_layout = isinstance(index, PackedTermShardedIndex)
    banded_layout = isinstance(index, BandedTermShardedIndex)
    lay = ("banded" if banded_layout
           else "packed" if packed_layout else "hor")
    arrs = index.device_arrays()
    num_docs, tile = index.num_docs, index.tile
    n_tiles = max(-(-num_docs // tile), 1)
    S = index.n_shards
    block = (index.block if packed_layout or banded_layout
             else int(index.block_docs.shape[-1]))
    m_blocks = max(index.max_blocks_per_term, 1)
    m_blocks_h = (max(index.hor_max_blocks_per_term, 1) if banded_layout
                  else 0)
    if cap is not None:
        m_blocks = max(min(m_blocks, -(-cap // block)), 1)
        m_blocks_h = max(min(m_blocks_h, -(-cap // block)), 1)
    # dense-score kernels: only the routing-free geometry (query-lane pad
    # and candidate quantum) follows the tuning table here
    cfg = autotune.lookup("pallas", num_docs, lay)
    q_pad = cfg.q_pad
    if cfg.tile == tile:
        k_tile = cfg.resolve_k_tile(k)
    else:
        k_tile = min(default_k_tile(k, tile, cfg.k_pad), tile)
    # per-shard slice of the tile grid for candidate extraction
    tiles_per = -(-n_tiles // S)
    chunk = tiles_per * tile

    names = ("sorted_hash", "df", "block_offsets", "tile_first",
             "tile_count")
    if banded_layout:
        names += ("packed", "tf_pairs", "block_bits", "block_base",
                  "block_count", "hor_block_offsets", "hor_block_docs",
                  "hor_block_tfs", "hor_tile_first", "hor_tile_count")
    elif packed_layout:
        names += ("packed", "tf_pairs", "block_bits", "block_base",
                  "block_count")
    else:
        names += ("block_docs", "block_tfs")
    sharded = {n: P(axis) for n in names}
    sharded["norm"] = P()

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(sharded, P()), out_specs=(P(), P(), P()),
        check_vma=False)
    def score(ix, qh):
        sq = {n: (v[0] if n != "norm" else v) for n, v in ix.items()}
        qh = dedup_query_hashes(qh)
        t = qh.shape[0]
        pos = jnp.searchsorted(sq["sorted_hash"], qh).astype(jnp.int32)
        pos = jnp.clip(pos, 0, sq["sorted_hash"].shape[0] - 1)
        hit = (sq["sorted_hash"][pos] == qh) & (qh != 0)
        tid = jnp.where(hit, pos, -1)       # terms NOT on this shard miss
        df = jnp.where(hit, sq["df"][pos], 0)
        w = idf_fn(df, num_docs)
        if cap is not None:
            # cap truncation on ANY shard is surfaced, never swallowed:
            # per-shard counts psum like the multi-segment conjunctive
            trunc = jax.lax.psum(
                jnp.sum((hit & (df > cap)).astype(jnp.int32)), axis)
        else:
            trunc = jnp.int32(0)

        cand_block, cand_valid, cand_q, cand_w, cand_cap = \
            expand_block_candidates(sq["block_offsets"], tid[None],
                                    w[None], m_blocks, block, cap=cap)
        max_pairs = max(min(index.route_pairs_max,
                            t * m_blocks * max(index.route_span_max, 1)), 8)
        pb, pt, pqw, pcap, ovf = build_batched_pairs(
            cand_block, cand_valid, cand_q, cand_w,
            sq["tile_first"], sq["tile_count"], n_tiles, 1, max_pairs,
            cand_cap=cand_cap)
        warn_on_overflow(ovf, "term-sharded fused engine")
        pqw = jnp.pad(pqw, ((0, 0), (0, q_pad - 1)))
        if packed_layout or banded_layout:
            partial = fused_score_packed_pallas(
                sq["packed"], sq["tf_pairs"], pb, pt, pqw, pcap,
                sq["block_bits"][pb], sq["block_base"][pb],
                sq["block_count"][pb], num_docs, block, tile)[0]
        else:
            partial = fused_score_blocked_pallas(
                sq["block_docs"], sq["block_tfs"], pb, pt, pqw, pcap,
                num_docs, tile)[0]
        if banded_layout:
            # every term is wholly in one band, so the HOR-band pass
            # scores exactly the terms the packed band skipped; the two
            # dense partials sum locally BEFORE the cross-shard psum
            cand_block, cand_valid, cand_q, cand_w, cand_cap = \
                expand_block_candidates(sq["hor_block_offsets"], tid[None],
                                        w[None], m_blocks_h, block, cap=cap)
            mp_h = max(min(index.hor_route_pairs_max,
                           t * m_blocks_h
                           * max(index.hor_route_span_max, 1)), 8)
            pb, pt, pqw, pcap, ovf = build_batched_pairs(
                cand_block, cand_valid, cand_q, cand_w,
                sq["hor_tile_first"], sq["hor_tile_count"], n_tiles, 1,
                mp_h, cand_cap=cand_cap)
            warn_on_overflow(ovf, "term-sharded fused engine")
            pqw = jnp.pad(pqw, ((0, 0), (0, q_pad - 1)))
            partial = partial + fused_score_blocked_pallas(
                sq["hor_block_docs"], sq["hor_block_tfs"], pb, pt, pqw,
                pcap, num_docs, tile)[0]
        # THE term-partitioned cost: a full [D] psum across shards
        scores = jax.lax.psum(partial, axis)
        qn2 = jax.lax.psum(jnp.sum(w * w), axis)
        qnorm = jnp.sqrt(jnp.maximum(qn2, 1e-12))
        live = sq["norm"] > 0
        final = jnp.where(live & (scores > 0),
                          scores / (jnp.maximum(sq["norm"], 1e-12) * qnorm),
                          -jnp.inf)
        s_idx = jax.lax.axis_index(axis)
        fpad = jnp.pad(final, (0, S * chunk - num_docs),
                       constant_values=-jnp.inf)
        local = jax.lax.dynamic_slice(fpad, (s_idx * chunk,), (chunk,))
        v, ids = extract_tile_candidates(local[None], tile, k_tile)
        gids = jnp.where(ids[0] >= 0, ids[0] + s_idx * chunk, -1)
        vv, ii = local_candidate_merge(v[0], gids, k, axis)
        return vv, ii, trunc

    fn = jax.jit(lambda qh: score(arrs, qh))

    def run(qh, trace=None):
        if trace is None:
            return fn(qh)
        span = trace.span("shard_fanout", parent="score", n_shards=S,
                          k=k, sharding="term", layout=lay)
        out = fn(qh)
        span.end()
        sync = trace.span("shard_sync", parent="score")
        out = jax.block_until_ready(out)
        sync.end()
        return out

    if return_stats:
        def with_stats(qh, trace=None):
            vv, ii, trunc = run(qh, trace=trace)
            trunc = int(trunc)
            record_truncated(trunc)
            return (vv, ii), {"truncated_terms": trunc}
        return with_stats

    def scorer(qh, trace=None):
        return run(qh, trace=trace)[:2]
    return scorer
