"""The paper's four index representations as TPU/HBM array layouts.

Paper -> TPU mapping (see DESIGN.md §2):

  PR   -> CooIndex        heap-of-tuples: postings stored in ARRIVAL (doc)
                          order as three parallel columns, plus a B+tree
                          analogue (a (term,doc)-sorted permutation with
                          per-term starts).  A term's postings are scattered
                          across the heap -> gathers are random-access, and
                          the term-id column is stored per posting.  This is
                          exactly why PR loses: redundant bytes + random I/O.

  OR   -> CsrIndex        postings packed contiguously per term (the
                          ARRAY-of-Point idea): offsets[W+1] + doc_ids[P] +
                          tfs[P].  A separate word table (hash->id, df)
                          remains, as in the paper's OR.

  COR  -> CompactCsrIndex word table folded into the posting relation: the
                          sorted term-hash array IS the lookup structure and
                          df lives alongside.  One fewer lookup phase.

  HOR  -> BlockedIndex    postings in fixed 128-lane blocks with per-block
                          doc-id min/max summaries: the TPU analogue of
                          hstore (keyed access within a term) + GIN (block
                          skipping for document-based probes).

  (beyond paper)
       -> PackedCsrIndex  delta + bit-packed doc ids, fp16 tf — the "special
                          number encodings" §3.1 says DBMSs lack.

All device structures are frozen dataclass pytrees of int32/float32 arrays;
builders are host-side numpy.  ``doc_ids`` within a term are always sorted
ascending (as a DBMS clustered index and every IR system guarantees).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import segments

Array = jax.Array

BLOCK = 128  # posting block size: one VPU lane-width / VMEM-friendly tile
ROUTE_TILE = 512  # doc-tile width the scoring kernels route against
LANES = 128  # a device row is moved by DMA in whole 128-lane widths


def _block_tile_routing(block_min: np.ndarray, block_max: np.ndarray,
                        num_docs: int, tile: int
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side pair-routing cache: per-block doc-tile span.

    The fused scoring kernel walks (block, tile) pairs; a block overlaps
    the contiguous tile range [min//tile, max//tile].  This was computed
    per query inside ``build_pairs`` — it is a pure function of the
    (immutable) index, so it is built ONCE here and stored on the index.
    Returns (tile_first i32[NB], tile_count i32[NB]); empty blocks
    (max < 0) get count 0.
    """
    n_tiles = max(-(-num_docs // tile), 1)
    has = block_max >= 0
    t0 = np.clip(block_min // tile, 0, n_tiles - 1)
    t1 = np.clip(block_max // tile, 0, n_tiles - 1)
    first = np.where(has, t0, 0).astype(np.int32)
    count = np.where(has, t1 - t0 + 1, 0).astype(np.int32)
    return first, count


def _register(cls):
    names = [f.name for f in dataclasses.fields(cls)]
    static = set(getattr(cls, "_static_fields", ()))
    jax.tree_util.register_dataclass(
        cls,
        data_fields=[n for n in names if n not in static],
        meta_fields=[n for n in names if n in static],
    )
    return cls


# ---------------------------------------------------------------------------
# shared tables
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DocTable:
    """Per-document metadata: the paper's ``document`` relation.  An
    index holds the row its ranking scores with (``core/ranking.py``):
    ``norm`` under tf-idf cosine, ``length`` under BM25, never both."""
    _static_fields = ()
    norm: Array | None   # f32[D]  vector norm under tf-idf (paper §3.6)
    rank: Array          # f32[D]  PageRank-like static score
    length: Array | None = None  # f32[D]  BM25's K(d) = k1 (1-b+b dl/avgdl)

    @property
    def num_docs(self) -> int:
        return self.rank.shape[0]

    def nbytes(self) -> int:
        return int(sum(x.nbytes for x in (self.norm, self.rank,
                                          self.length) if x is not None))


_register(DocTable)


@dataclasses.dataclass(frozen=True)
class SortedLookup:
    """B+tree analogue: binary search over sorted term hashes."""
    _static_fields = ()
    sorted_hash: Array  # u32[W] ascending
    perm: Array         # i32[W] sorted position -> term id

    def lookup(self, hashes: Array) -> Array:
        """u32[T] -> term ids i32[T], -1 where absent."""
        pos = jnp.searchsorted(self.sorted_hash, hashes).astype(jnp.int32)
        pos = jnp.clip(pos, 0, self.sorted_hash.shape[0] - 1)
        hit = self.sorted_hash[pos] == hashes
        return jnp.where(hit, self.perm[pos], -1)

    def nbytes(self) -> int:
        return int(self.sorted_hash.nbytes + self.perm.nbytes)


_register(SortedLookup)

HASH_EMPTY = np.uint32(0xFFFFFFFF)
MAX_PROBES = 16


@dataclasses.dataclass(frozen=True)
class HashLookup:
    """Open-addressed hash table analogue of a DBMS Hash index."""
    _static_fields = ()
    keys: Array   # u32[S], HASH_EMPTY where empty; S power of two
    vals: Array   # i32[S]

    def lookup(self, hashes: Array) -> Array:
        size = self.keys.shape[0]
        mask = jnp.uint32(size - 1)
        base = (hashes * jnp.uint32(2654435761)) & mask
        # vectorized probe: MAX_PROBES slots per query
        probe = (base[:, None] + jnp.arange(MAX_PROBES, dtype=jnp.uint32)[None, :]) & mask
        kk = self.keys[probe]                       # [T, MAX_PROBES]
        hit = kk == hashes[:, None]
        any_hit = jnp.any(hit, axis=1)
        first = jnp.argmax(hit, axis=1)
        slot = jnp.take_along_axis(probe, first[:, None], axis=1)[:, 0]
        return jnp.where(any_hit, self.vals[slot], -1).astype(jnp.int32)

    def nbytes(self) -> int:
        return int(self.keys.nbytes + self.vals.nbytes)


_register(HashLookup)


def build_sorted_lookup(term_hashes: np.ndarray) -> SortedLookup:
    order = np.argsort(term_hashes, kind="stable")
    return SortedLookup(
        sorted_hash=jnp.asarray(term_hashes[order].astype(np.uint32)),
        perm=jnp.asarray(order.astype(np.int32)),
    )


def build_hash_lookup(term_hashes: np.ndarray) -> HashLookup:
    w = len(term_hashes)
    size = 1 << int(np.ceil(np.log2(max(4 * w, 16))))
    while True:
        keys = np.full(size, HASH_EMPTY, dtype=np.uint32)
        vals = np.full(size, -1, dtype=np.int32)
        ok = True
        base = (term_hashes.astype(np.uint64) * 2654435761) % size
        for tid, b in enumerate(base.astype(np.int64)):
            placed = False
            for p in range(MAX_PROBES):
                s = (b + p) & (size - 1)
                if keys[s] == HASH_EMPTY:
                    keys[s] = term_hashes[tid]
                    vals[s] = tid
                    placed = True
                    break
            if not placed:
                ok = False
                break
        if ok:
            return HashLookup(keys=jnp.asarray(keys), vals=jnp.asarray(vals))
        size *= 2  # grow until every key fits within MAX_PROBES


# ---------------------------------------------------------------------------
# Postings source-of-truth (host-side) used by all builders
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PostingsHost:
    """Host (numpy) canonical postings: the logical index content."""
    term_hashes: np.ndarray   # u32[W]  hash of each term (id == position)
    df: np.ndarray            # i32[W]
    # CSR over terms (term-major, doc-sorted within term):
    offsets: np.ndarray       # i64[W+1]
    doc_ids: np.ndarray       # i32[P]
    tfs: np.ndarray           # f32[P]
    num_docs: int
    norm: np.ndarray          # f32[D]
    rank: np.ndarray          # f32[D]

    @property
    def num_terms(self) -> int:
        return len(self.term_hashes)

    @property
    def num_postings(self) -> int:
        return len(self.doc_ids)

    @property
    def max_posting_len(self) -> int:
        if self.num_terms == 0:
            return 0
        return int((self.offsets[1:] - self.offsets[:-1]).max())


# ---------------------------------------------------------------------------
# (PR) CooIndex
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CooIndex:
    """Plain-Relational analogue: heap-of-tuples + B+tree permutation."""
    _static_fields = ("max_posting_len",)
    # heap columns, in arrival (doc-major) order — like tuples in a heap file
    word_ids: Array   # i32[P]  <- the redundant column PR pays for
    doc_ids: Array    # i32[P]
    tfs: Array        # f32[P]
    # "B+tree": (term,doc)-sorted permutation + per-term starts
    perm: Array         # i32[P] sorted posting -> heap position
    term_starts: Array  # i32[W+1]
    df: Array           # i32[W]
    lookup: SortedLookup | HashLookup
    docs: DocTable
    max_posting_len: int

    @property
    def num_terms(self) -> int:
        return self.df.shape[0]

    def lookup_terms(self, hashes: Array) -> Array:
        return self.lookup.lookup(hashes)

    def term_df(self, term_ids: Array) -> Array:
        safe = jnp.maximum(term_ids, 0)
        return jnp.where(term_ids >= 0, self.df[safe], 0)

    def gather_postings(self, term_ids: Array, cap: int
                        ) -> Tuple[Array, Array, Array]:
        """q_occ for PR: read index leaves (perm) then RANDOM heap gathers."""
        safe = jnp.maximum(term_ids, 0)

        def one(tid):
            idx, valid = segments.gather_segment(self.perm, self.term_starts,
                                                 tid, cap)
            d = jnp.take(self.doc_ids, idx, axis=0)
            t = jnp.take(self.tfs, idx, axis=0)
            # PR also streams the word_id column through the memory system;
            # touch it so the cost is real, then mask it out.
            w = jnp.take(self.word_ids, idx, axis=0)
            t = t + 0.0 * w.astype(t.dtype)
            d = jnp.where(valid, d, -1)
            t = jnp.where(valid, t, 0.0)
            return d, t, valid

        d, t, v = jax.vmap(one)(safe)
        present = (term_ids >= 0)[:, None]
        return jnp.where(present, d, -1), jnp.where(present, t, 0.0), v & present

    def nbytes(self) -> int:
        n = sum(int(x.nbytes) for x in
                (self.word_ids, self.doc_ids, self.tfs, self.perm,
                 self.term_starts, self.df))
        return n + self.lookup.nbytes() + self.docs.nbytes()

    def posting_bytes(self) -> int:
        return int(self.word_ids.nbytes + self.doc_ids.nbytes +
                   self.tfs.nbytes + self.perm.nbytes)


_register(CooIndex)


def build_coo(h: PostingsHost, lookup: str = "btree") -> CooIndex:
    P = h.num_postings
    # heap order = arrival order = doc-major: sort canonical (term-major)
    # postings by (doc, term) to synthesize the heap.
    term_of = np.repeat(np.arange(h.num_terms, dtype=np.int64),
                        np.diff(h.offsets))
    heap_order = np.lexsort((term_of, h.doc_ids))      # doc-major heap
    heap_word = term_of[heap_order].astype(np.int32)
    heap_doc = h.doc_ids[heap_order].astype(np.int32)
    heap_tf = h.tfs[heap_order].astype(np.float32)
    # B+tree: sort heap positions by (term, doc)
    perm = np.lexsort((heap_doc, heap_word)).astype(np.int32)
    starts = np.searchsorted(heap_word[perm], np.arange(h.num_terms + 1))
    lk = (build_sorted_lookup(h.term_hashes) if lookup == "btree"
          else build_hash_lookup(h.term_hashes))
    return CooIndex(
        word_ids=jnp.asarray(heap_word), doc_ids=jnp.asarray(heap_doc),
        tfs=jnp.asarray(heap_tf), perm=jnp.asarray(perm),
        term_starts=jnp.asarray(starts.astype(np.int32)),
        df=jnp.asarray(h.df.astype(np.int32)), lookup=lk,
        docs=DocTable(norm=jnp.asarray(h.norm), rank=jnp.asarray(h.rank)),
        max_posting_len=h.max_posting_len,
    )


# ---------------------------------------------------------------------------
# (OR) CsrIndex
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CsrIndex:
    """Object-Relational analogue: contiguous per-term posting slabs."""
    _static_fields = ("max_posting_len",)
    offsets: Array   # i32[W+1]
    doc_ids: Array   # i32[P]
    tfs: Array       # f32[P]
    df: Array        # i32[W]   (separate word table, as in OR)
    lookup: SortedLookup | HashLookup
    docs: DocTable
    max_posting_len: int

    @property
    def num_terms(self) -> int:
        return self.df.shape[0]

    def lookup_terms(self, hashes: Array) -> Array:
        return self.lookup.lookup(hashes)

    def term_df(self, term_ids: Array) -> Array:
        safe = jnp.maximum(term_ids, 0)
        return jnp.where(term_ids >= 0, self.df[safe], 0)

    def gather_postings(self, term_ids: Array, cap: int
                        ) -> Tuple[Array, Array, Array]:
        """q_occ for ORIF: one contiguous slab DMA per term."""
        safe = jnp.maximum(term_ids, 0)
        d, v = segments.gather_segments(self.doc_ids, self.offsets, safe, cap,
                                        fill=-1)
        t, _ = segments.gather_segments(self.tfs, self.offsets, safe, cap,
                                        fill=0.0)
        present = (term_ids >= 0)[:, None]
        return (jnp.where(present, d, -1), jnp.where(present, t, 0.0),
                v & present)

    def nbytes(self) -> int:
        n = sum(int(x.nbytes) for x in
                (self.offsets, self.doc_ids, self.tfs, self.df))
        return n + self.lookup.nbytes() + self.docs.nbytes()

    def posting_bytes(self) -> int:
        return int(self.offsets.nbytes + self.doc_ids.nbytes + self.tfs.nbytes)


_register(CsrIndex)


def build_csr(h: PostingsHost, lookup: str = "btree") -> CsrIndex:
    lk = (build_sorted_lookup(h.term_hashes) if lookup == "btree"
          else build_hash_lookup(h.term_hashes))
    return CsrIndex(
        offsets=jnp.asarray(h.offsets.astype(np.int32)),
        doc_ids=jnp.asarray(h.doc_ids.astype(np.int32)),
        tfs=jnp.asarray(h.tfs.astype(np.float32)),
        df=jnp.asarray(h.df.astype(np.int32)), lookup=lk,
        docs=DocTable(norm=jnp.asarray(h.norm), rank=jnp.asarray(h.rank)),
        max_posting_len=h.max_posting_len,
    )


# ---------------------------------------------------------------------------
# (COR) CompactCsrIndex
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CompactCsrIndex:
    """Compact OR: word table folded into the posting relation.

    Terms are stored in HASH-SORTED order; the sorted hash array doubles as
    the lookup structure (no separate word table), and df sits alongside.
    q_word and q_occ fuse into a single phase — the paper's "one fewer
    query".
    """
    _static_fields = ("max_posting_len",)
    sorted_hash: Array  # u32[W]
    df: Array           # i32[W]   (aligned with sorted_hash)
    offsets: Array      # i32[W+1] (aligned with sorted_hash)
    doc_ids: Array      # i32[P]
    tfs: Array          # f32[P]
    docs: DocTable
    max_posting_len: int

    @property
    def num_terms(self) -> int:
        return self.df.shape[0]

    def lookup_terms(self, hashes: Array) -> Array:
        pos = jnp.searchsorted(self.sorted_hash, hashes).astype(jnp.int32)
        pos = jnp.clip(pos, 0, self.sorted_hash.shape[0] - 1)
        hit = self.sorted_hash[pos] == hashes
        return jnp.where(hit, pos, -1)

    def term_df(self, term_ids: Array) -> Array:
        safe = jnp.maximum(term_ids, 0)
        return jnp.where(term_ids >= 0, self.df[safe], 0)

    def gather_postings(self, term_ids: Array, cap: int
                        ) -> Tuple[Array, Array, Array]:
        safe = jnp.maximum(term_ids, 0)
        d, v = segments.gather_segments(self.doc_ids, self.offsets, safe, cap,
                                        fill=-1)
        t, _ = segments.gather_segments(self.tfs, self.offsets, safe, cap,
                                        fill=0.0)
        present = (term_ids >= 0)[:, None]
        return (jnp.where(present, d, -1), jnp.where(present, t, 0.0),
                v & present)

    def nbytes(self) -> int:
        return sum(int(x.nbytes) for x in
                   (self.sorted_hash, self.df, self.offsets, self.doc_ids,
                    self.tfs)) + self.docs.nbytes()

    def posting_bytes(self) -> int:
        return int(self.offsets.nbytes + self.doc_ids.nbytes + self.tfs.nbytes)


_register(CompactCsrIndex)


def build_compact_csr(h: PostingsHost) -> CompactCsrIndex:
    order = np.argsort(h.term_hashes, kind="stable")
    lengths = np.diff(h.offsets)[order]
    new_offsets = np.zeros(h.num_terms + 1, dtype=np.int64)
    np.cumsum(lengths, out=new_offsets[1:])
    P = h.num_postings
    doc_ids = np.empty(P, dtype=np.int32)
    tfs = np.empty(P, dtype=np.float32)
    for newpos, old in enumerate(order):          # permute slabs
        s, e = h.offsets[old], h.offsets[old + 1]
        ns = new_offsets[newpos]
        doc_ids[ns:ns + (e - s)] = h.doc_ids[s:e]
        tfs[ns:ns + (e - s)] = h.tfs[s:e]
    return CompactCsrIndex(
        sorted_hash=jnp.asarray(h.term_hashes[order].astype(np.uint32)),
        df=jnp.asarray(h.df[order].astype(np.int32)),
        offsets=jnp.asarray(new_offsets.astype(np.int32)),
        doc_ids=jnp.asarray(doc_ids), tfs=jnp.asarray(tfs),
        docs=DocTable(norm=jnp.asarray(h.norm), rank=jnp.asarray(h.rank)),
        max_posting_len=h.max_posting_len,
    )


# ---------------------------------------------------------------------------
# (HOR) BlockedIndex
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BlockedIndex:
    """hstore/GIN analogue: fixed-size posting blocks + per-block summaries.

    Each term's postings are rounded up to multiples of BLOCK lanes
    (padding doc_id = -1, tf = 0).  Per block we keep min/max doc id —
    enabling (a) block-skipping doc-membership probes (document-based
    access, paper §4.4 / GIN) and (b) aligned VMEM tiles for the Pallas
    scoring kernel.
    """
    _static_fields = ("max_posting_len", "max_blocks_per_term", "block",
                      "route_tile", "route_pairs_max", "route_span_max")
    sorted_hash: Array    # u32[W]  (COR-style folded word table)
    df: Array             # i32[W]
    block_offsets: Array  # i32[W+1]  term -> block range
    block_docs: Array     # i32[NB, BLOCK]  (-1 padding)
    block_tfs: Array      # f32[NB, BLOCK]
    block_min: Array      # i32[NB]
    block_max: Array      # i32[NB]
    docs: DocTable
    max_posting_len: int
    max_blocks_per_term: int
    block: int = BLOCK
    # pair-routing cache (block -> doc-tile span at route_tile width)
    tile_first: Array | None = None   # i32[NB]
    tile_count: Array | None = None   # i32[NB]
    route_tile: int = ROUTE_TILE
    route_pairs_max: int = 0   # sum(tile_count): dedup upper bound on pairs
    route_span_max: int = 0    # max(tile_count): worst span of one block

    @property
    def num_terms(self) -> int:
        return self.df.shape[0]

    def lookup_terms(self, hashes: Array) -> Array:
        pos = jnp.searchsorted(self.sorted_hash, hashes).astype(jnp.int32)
        pos = jnp.clip(pos, 0, self.sorted_hash.shape[0] - 1)
        hit = self.sorted_hash[pos] == hashes
        return jnp.where(hit, pos, -1)

    def term_df(self, term_ids: Array) -> Array:
        safe = jnp.maximum(term_ids, 0)
        return jnp.where(term_ids >= 0, self.df[safe], 0)

    def gather_postings(self, term_ids: Array, cap: int
                        ) -> Tuple[Array, Array, Array]:
        nblk = -(-cap // self.block)
        safe = jnp.maximum(term_ids, 0)

        def one(tid):
            start = self.block_offsets[tid]
            nb = self.block_offsets[tid + 1] - start
            bidx = start + jnp.arange(nblk, dtype=jnp.int32)
            bvalid = jnp.arange(nblk, dtype=jnp.int32) < nb
            bidx = jnp.where(bvalid, bidx, 0)
            d = jnp.take(self.block_docs, bidx, axis=0)   # [nblk, BLOCK]
            t = jnp.take(self.block_tfs, bidx, axis=0)
            d = jnp.where(bvalid[:, None], d, -1).reshape(-1)
            t = jnp.where(bvalid[:, None], t, 0.0).reshape(-1)
            return d[:cap], t[:cap]

        d, t = jax.vmap(one)(safe)
        present = (term_ids >= 0)[:, None]
        v = (d >= 0) & present
        return jnp.where(present, d, -1), jnp.where(present, t, 0.0), v

    def contains(self, term_ids: Array, doc_id: Array) -> Array:
        """Doc-membership probe with block skipping (the GIN-style path)."""
        safe = jnp.maximum(term_ids, 0)
        nblk = self.max_blocks_per_term

        def one(tid):
            start = self.block_offsets[tid]
            nb = self.block_offsets[tid + 1] - start
            bidx = start + jnp.arange(nblk, dtype=jnp.int32)
            bvalid = jnp.arange(nblk, dtype=jnp.int32) < nb
            bidx = jnp.where(bvalid, bidx, 0)
            hit_range = (self.block_min[bidx] <= doc_id) & \
                        (self.block_max[bidx] >= doc_id) & bvalid
            # only blocks whose [min,max] covers doc_id are inspected
            d = jnp.take(self.block_docs, bidx, axis=0)
            inblock = jnp.any(d == doc_id, axis=1)
            return jnp.any(hit_range & inblock)

        found = jax.vmap(one)(safe)
        return found & (term_ids >= 0)

    def nbytes(self) -> int:
        return sum(int(x.nbytes) for x in
                   (self.sorted_hash, self.df, self.block_offsets,
                    self.block_docs, self.block_tfs, self.block_min,
                    self.block_max)) + self.docs.nbytes()

    def posting_bytes(self) -> int:
        return int(self.block_offsets.nbytes + self.block_docs.nbytes +
                   self.block_tfs.nbytes + self.block_min.nbytes +
                   self.block_max.nbytes)


_register(BlockedIndex)


def build_blocked(h: PostingsHost, block: int = BLOCK,
                  route_tile: int = ROUTE_TILE) -> BlockedIndex:
    """``route_tile`` sets the doc-tile width of the build-time pair-
    routing cache; the seal path passes the autotuned tile for the
    segment's size class so sealed segments are born pre-tuned."""
    order = np.argsort(h.term_hashes, kind="stable")
    lengths = np.diff(h.offsets)[order]
    nblocks = -(-lengths // block)
    nblocks = np.maximum(nblocks, (lengths > 0).astype(nblocks.dtype))
    block_offsets = np.zeros(h.num_terms + 1, dtype=np.int64)
    np.cumsum(nblocks, out=block_offsets[1:])
    NB = int(block_offsets[-1])
    bd = np.full((NB, block), -1, dtype=np.int32)
    bt = np.zeros((NB, block), dtype=np.float32)
    P = h.num_postings
    if P:
        # vectorized block fill (one fancy-index scatter instead of a
        # per-term python loop — that loop dominated live-index seal
        # wall time): every posting's destination (block row, lane) is a
        # pure function of its rank within its (hash-sorted) term
        new_offsets = np.zeros(h.num_terms + 1, dtype=np.int64)
        np.cumsum(lengths, out=new_offsets[1:])
        starts_src = h.offsets[order].astype(np.int64)   # old slab starts
        within = np.arange(P, dtype=np.int64) - np.repeat(new_offsets[:-1],
                                                          lengths)
        src = np.repeat(starts_src, lengths) + within
        brow = np.repeat(block_offsets[:-1], lengths) + within // block
        lane = within % block
        bd[brow, lane] = h.doc_ids[src]
        bt[brow, lane] = h.tfs[src]
    bmin = np.where((bd >= 0).any(axis=1),
                    np.where(bd >= 0, bd, np.iinfo(np.int32).max).min(axis=1),
                    0).astype(np.int32)
    bmax = bd.max(axis=1).astype(np.int32)
    tfirst, tcount = _block_tile_routing(bmin, bmax, h.num_docs, route_tile)
    return BlockedIndex(
        sorted_hash=jnp.asarray(h.term_hashes[order].astype(np.uint32)),
        df=jnp.asarray(h.df[order].astype(np.int32)),
        block_offsets=jnp.asarray(block_offsets.astype(np.int32)),
        block_docs=jnp.asarray(bd), block_tfs=jnp.asarray(bt),
        block_min=jnp.asarray(bmin), block_max=jnp.asarray(bmax),
        docs=DocTable(norm=jnp.asarray(h.norm), rank=jnp.asarray(h.rank)),
        max_posting_len=h.max_posting_len,
        max_blocks_per_term=int(nblocks.max()) if len(nblocks) else 0,
        block=block,
        tile_first=jnp.asarray(tfirst), tile_count=jnp.asarray(tcount),
        route_tile=int(route_tile),
        route_pairs_max=int(tcount.sum()),
        route_span_max=int(tcount.max()) if len(tcount) else 0,
    )


def size_class(n: int, base: int = 128, growth: int = 2) -> int:
    """Smallest ``base * growth**i >= max(n, 1)`` — the static size-class
    quantizer the live index seals segments into.

    Device shapes (and the jit static metadata derived from them) are
    quantized to a few geometric classes so that sealing a new segment
    reuses an already-compiled kernel instead of triggering an XLA
    recompile: two segments in the same class share one compilation.
    """
    n = max(int(n), 1)
    c = base
    while c < n:
        c *= growth
    return c


def pad_blocked_to_class(ix: BlockedIndex, nb_pad: int, w_pad: int,
                         max_posting_len: int, max_blocks_per_term: int,
                         route_pairs_max: int, route_span_max: int
                         ) -> BlockedIndex:
    """Pad a BlockedIndex to a static size class.

    Arrays grow to (nb_pad blocks, w_pad terms) with inert padding
    (empty blocks with tile_count 0, absent-hash vocabulary slots) and
    the static metadata is OVERRIDDEN with quantized upper bounds
    (``>=`` the real values — each is only ever used as a budget or loop
    bound, so over-approximating is semantically safe).  Every padded
    field participates in the jit signature; quantizing all of them is
    what makes "seal a segment, query it, no new compilation" hold.
    The doc-space padding (``docs.num_docs``) is chosen at build time by
    the caller (a tile-aligned class), not here.
    """
    w, nb = ix.num_terms, int(ix.block_docs.shape[0])
    if nb_pad < nb or w_pad < w:
        raise ValueError(f"size class ({nb_pad}, {w_pad}) below actual "
                         f"({nb}, {w})")
    if (max_posting_len < ix.max_posting_len
            or max_blocks_per_term < ix.max_blocks_per_term
            or route_pairs_max < ix.route_pairs_max
            or route_span_max < ix.route_span_max):
        raise ValueError("quantized static bounds must cover the actual "
                         "index statics")
    dn, dw = nb_pad - nb, w_pad - w
    last = ix.block_offsets[-1]
    return dataclasses.replace(
        ix,
        sorted_hash=jnp.pad(ix.sorted_hash, (0, dw),
                            constant_values=HASH_EMPTY),
        df=jnp.pad(ix.df, (0, dw)),
        block_offsets=jnp.pad(ix.block_offsets, (0, dw),
                              constant_values=last),
        block_docs=jnp.pad(ix.block_docs, ((0, dn), (0, 0)),
                           constant_values=-1),
        block_tfs=jnp.pad(ix.block_tfs, ((0, dn), (0, 0))),
        block_min=jnp.pad(ix.block_min, (0, dn)),
        block_max=jnp.pad(ix.block_max, (0, dn), constant_values=-1),
        tile_first=jnp.pad(ix.tile_first, (0, dn)),
        tile_count=jnp.pad(ix.tile_count, (0, dn)),
        max_posting_len=int(max_posting_len),
        max_blocks_per_term=int(max_blocks_per_term),
        route_pairs_max=int(route_pairs_max),
        route_span_max=int(route_span_max),
    )


def pad_packed_to_class(ix: "PackedCsrIndex", nb_pad: int, w_pad: int,
                        max_posting_len: int, words_per_block: int,
                        route_pairs_max: int, route_span_max: int
                        ) -> "PackedCsrIndex":
    """Pad a PackedCsrIndex to a static size class (the packed twin of
    ``pad_blocked_to_class``, for delta+bit-packed sealed segments).

    Padding blocks are inert: bit width 1 (in-distribution for the
    decoder), count 0 (every lane decodes invalid), tile_count 0 (never
    routed).  ``words_per_block`` is shape-bearing (the packed array's
    lane dim), so it quantizes like the other statics.
    """
    w, nb = ix.num_terms, int(ix.packed.shape[0])
    wpb = int(ix.words_per_block)
    if nb_pad < nb or w_pad < w or words_per_block < wpb:
        raise ValueError(f"size class ({nb_pad}, {w_pad}, {words_per_block})"
                         f" below actual ({nb}, {w}, {wpb})")
    if (max_posting_len < ix.max_posting_len
            or route_pairs_max < ix.route_pairs_max
            or route_span_max < ix.route_span_max):
        raise ValueError("quantized static bounds must cover the actual "
                         "index statics")
    dn, dw = nb_pad - nb, w_pad - w
    last = ix.block_offsets[-1]
    return dataclasses.replace(
        ix,
        sorted_hash=jnp.pad(ix.sorted_hash, (0, dw),
                            constant_values=HASH_EMPTY),
        df=jnp.pad(ix.df, (0, dw)),
        block_offsets=jnp.pad(ix.block_offsets, (0, dw),
                              constant_values=last),
        block_bits=jnp.pad(ix.block_bits, (0, dn), constant_values=1),
        block_base=jnp.pad(ix.block_base, (0, dn)),
        block_count=jnp.pad(ix.block_count, (0, dn)),
        packed=jnp.pad(ix.packed, (
            (0, dn), (0, lane_width(words_per_block) - ix.packed.shape[1]))),
        tf_pairs=jnp.pad(ix.tf_pairs, (
            (0, -(-nb_pad // 2) - ix.tf_pairs.shape[0]), (0, 0))),
        block_min=jnp.pad(ix.block_min, (0, dn)),
        block_max=jnp.pad(ix.block_max, (0, dn), constant_values=-1),
        tile_first=jnp.pad(ix.tile_first, (0, dn)),
        tile_count=jnp.pad(ix.tile_count, (0, dn)),
        max_posting_len=int(max_posting_len),
        words_per_block=int(words_per_block),
        route_pairs_max=int(route_pairs_max),
        route_span_max=int(route_span_max),
    )


# ---------------------------------------------------------------------------
# (beyond paper) PackedCsrIndex — delta + bit-packed postings
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PackedCsrIndex:
    """Delta+bit-packed doc ids per 128-posting block, fp16 tf.

    The paper (§3.1) notes DBMSs cannot apply the number encodings that
    make inverted files small.  On TPU we can: each block of 128 doc-id
    deltas is packed at a per-block bit width into int32 words; the
    fused kernels (kernels/fused_decode_score.py) unpack blocks in VMEM.
    First entry of each block stores the absolute doc id's delta from
    ``block_base``.

    Rows are stored in the form the kernels DMA, so no call copies the
    index: a block's words fill the first ``words_per_block`` lanes of a
    ``lane_width(words_per_block)``-lane row (a TPU lays an array's
    minor dim out in 128-lane tiles anyway), and the f16 tfs of blocks
    ``2r`` and ``2r + 1`` share u32 row ``r`` of ``tf_pairs`` (low and
    high half of each lane): f16 bytes in 32-bit rows, since one DMA
    cannot move a single 16-bit row.  ``posting_bytes`` counts the
    format (``words_per_block`` words and 2 bytes of tf per posting
    slot); ``nbytes`` counts the stored arrays.
    """
    _static_fields = ("max_posting_len", "words_per_block", "block",
                      "route_tile", "route_pairs_max", "route_span_max")
    sorted_hash: Array    # u32[W]
    df: Array             # i32[W]
    block_offsets: Array  # i32[W+1]    term -> block range
    block_bits: Array     # i32[NB]     bit width of this block
    block_base: Array     # i32[NB]     absolute doc id before first entry
    block_count: Array    # i32[NB]     valid postings in this block
    packed: Array         # u32[NB, lane_width(words_per_block)]
    tf_pairs: Array       # u32[ceil(NB/2), BLOCK]  f16 tf bits, 2 blocks
    docs: DocTable
    max_posting_len: int
    words_per_block: int
    block: int = BLOCK
    # per-block doc-id summaries + pair-routing cache (as in BlockedIndex;
    # for packed blocks these are only recoverable by decoding, so they
    # MUST be captured at build time)
    block_min: Array | None = None    # i32[NB]
    block_max: Array | None = None    # i32[NB]
    tile_first: Array | None = None   # i32[NB]
    tile_count: Array | None = None   # i32[NB]
    route_tile: int = ROUTE_TILE
    route_pairs_max: int = 0
    route_span_max: int = 0

    @property
    def num_terms(self) -> int:
        return self.df.shape[0]

    @property
    def max_blocks_per_term(self) -> int:
        """Worst-case posting blocks one term spans — the BlockedIndex
        field's packed twin, derived from the (possibly size-class
        quantized) posting-length bound.  Used as the per-term candidate
        fan-out bound by the sharded fused engines, which accept either
        layout."""
        return max(-(-self.max_posting_len // self.block), 1)

    def lookup_terms(self, hashes: Array) -> Array:
        pos = jnp.searchsorted(self.sorted_hash, hashes).astype(jnp.int32)
        pos = jnp.clip(pos, 0, self.sorted_hash.shape[0] - 1)
        hit = self.sorted_hash[pos] == hashes
        return jnp.where(hit, pos, -1)

    def term_df(self, term_ids: Array) -> Array:
        safe = jnp.maximum(term_ids, 0)
        return jnp.where(term_ids >= 0, self.df[safe], 0)

    def unpack_block(self, b: Array) -> Tuple[Array, Array, Array]:
        """Decode one block -> (doc_ids[BLOCK], tfs[BLOCK], valid[BLOCK])."""
        bits = self.block_bits[b]
        words = self.packed[b]                       # u32[words_per_block]
        lane = jnp.arange(self.block, dtype=jnp.uint32)
        bitpos = lane * bits.astype(jnp.uint32)
        wi = (bitpos >> 5).astype(jnp.int32)
        off = bitpos & jnp.uint32(31)
        lo = words[wi] >> off
        hi_valid = off > 0
        hi = jnp.where(hi_valid,
                       words[jnp.minimum(wi + 1, words.shape[0] - 1)]
                       << (jnp.uint32(32) - off), jnp.uint32(0))
        raw = lo | hi
        mask = jnp.where(bits >= 32, jnp.uint32(0xFFFFFFFF),
                         (jnp.uint32(1) << bits.astype(jnp.uint32)) - 1)
        deltas = (raw & mask).astype(jnp.int32)
        docs = self.block_base[b] + jnp.cumsum(deltas, dtype=jnp.int32)
        valid = jnp.arange(self.block, dtype=jnp.int32) < self.block_count[b]
        docs = jnp.where(valid, docs, -1)
        tfs = jnp.where(valid, unpair_tfs(self.tf_pairs, b), 0.0)
        return docs, tfs, valid

    def gather_postings(self, term_ids: Array, cap: int
                        ) -> Tuple[Array, Array, Array]:
        nblk = -(-cap // self.block)
        safe = jnp.maximum(term_ids, 0)

        def one(tid):
            start = self.block_offsets[tid]
            nb = self.block_offsets[tid + 1] - start
            bidx = start + jnp.arange(nblk, dtype=jnp.int32)
            bvalid = jnp.arange(nblk, dtype=jnp.int32) < nb
            bidx = jnp.where(bvalid, bidx, 0)
            d, t, v = jax.vmap(self.unpack_block)(bidx)
            d = jnp.where(bvalid[:, None], d, -1).reshape(-1)
            t = jnp.where(bvalid[:, None], t, 0.0).reshape(-1)
            v = (bvalid[:, None] & v).reshape(-1)
            return d[:cap], t[:cap], v[:cap]

        d, t, v = jax.vmap(one)(safe)
        present = (term_ids >= 0)[:, None]
        return (jnp.where(present, d, -1), jnp.where(present, t, 0.0),
                v & present)

    def nbytes(self) -> int:
        return sum(int(x.nbytes) for x in
                   (self.sorted_hash, self.df, self.block_offsets,
                    self.block_bits, self.block_base, self.block_count,
                    self.packed, self.tf_pairs)) + self.docs.nbytes()

    def posting_bytes(self) -> int:
        nb = int(self.packed.shape[0])
        return int(self.block_offsets.nbytes + self.block_bits.nbytes +
                   self.block_base.nbytes + self.block_count.nbytes +
                   nb * (4 * self.words_per_block + 2 * self.block))


_register(PackedCsrIndex)


def lane_width(words_per_block: int) -> int:
    """Stored lane width of a packed word row: the word count rounded
    up to whole 128-lane rows, the unit one DMA moves."""
    return -(-max(int(words_per_block), 1) // LANES) * LANES


def pair_tf_rows(tfs: np.ndarray) -> np.ndarray:
    """f16 tfs ``[NB, B]`` -> u32 ``[ceil(NB/2), B]`` rows: lane ``l`` of
    row ``r`` holds block ``2r``'s tf bits in its low half and block
    ``2r + 1``'s in its high half."""
    t = np.asarray(tfs, np.float16).view(np.uint16)
    if t.shape[0] % 2:
        t = np.concatenate([t, np.zeros((1, t.shape[1]), np.uint16)])
    return t[0::2].astype(np.uint32) | (t[1::2].astype(np.uint32) << 16)


def unpair_tfs(tf_pairs: Array, b: Array) -> Array:
    """f32 tfs ``[..., B]`` of blocks ``b`` (any int shape) from
    ``pair_tf_rows`` rows: the half ``b & 1`` of row ``b >> 1``."""
    rows = tf_pairs[b >> 1]
    shift = ((b & 1) * 16).astype(jnp.uint32)[..., None]
    half = ((rows >> shift) & jnp.uint32(0xFFFF)).astype(jnp.uint16)
    return jax.lax.bitcast_convert_type(half, jnp.float16).astype(
        jnp.float32)


def _pack_block_np(deltas: np.ndarray, bits: int, block: int = BLOCK
                   ) -> np.ndarray:
    """Pack ``block`` deltas of ``bits`` width into u32 words."""
    out = np.zeros((block * bits + 31) // 32, dtype=np.uint64)
    for i, dv in enumerate(deltas.astype(np.uint64)):
        bitpos = i * bits
        wi, off = divmod(bitpos, 32)
        out[wi] |= (dv << off) & 0xFFFFFFFF
        spill = dv >> (32 - off) if off else 0
        if spill and wi + 1 < len(out):
            out[wi + 1] |= spill
    return out.astype(np.uint32)


def build_packed_csr(h: PostingsHost, max_bits: int = 32,
                     block: int = BLOCK,
                     route_tile: int = ROUTE_TILE) -> PackedCsrIndex:
    """Delta+bit-pack every term's posting blocks, vectorized over all
    postings (the words are those ``_pack_block_np`` writes per block):
    a block's first delta is taken against the previous block's last doc
    id (``-1`` at term start), its width is the bit length of its
    largest delta clipped to [1, max_bits], and lane ``l`` occupies bits
    ``[l*width, (l+1)*width)`` of the block's little-endian word
    stream."""
    order = np.argsort(h.term_hashes, kind="stable")
    lengths = np.diff(h.offsets)[order].astype(np.int64)
    nblocks = np.maximum(-(-lengths // block), (lengths > 0).astype(np.int64))
    block_offsets = np.zeros(h.num_terms + 1, dtype=np.int64)
    np.cumsum(nblocks, out=block_offsets[1:])
    NB = int(block_offsets[-1])
    # every posting in sorted-term order: its term, its rank in the term
    term_of = np.repeat(np.arange(h.num_terms, dtype=np.int64), lengths)
    new_start = np.cumsum(lengths) - lengths
    within = np.arange(term_of.shape[0], dtype=np.int64) - new_start[term_of]
    src = h.offsets[:-1][order].astype(np.int64)[term_of] + within
    docs = h.doc_ids[src].astype(np.int64)
    blk = block_offsets[:-1][term_of] + within // block
    lane = within % block
    prev = np.empty_like(docs)
    prev[1:] = docs[:-1]
    prev[within == 0] = -1                  # a term's first delta: vs -1
    deltas = docs - prev
    bstart = np.flatnonzero(lane == 0)      # blocks are contiguous runs
    bend = np.append(bstart[1:], docs.shape[0])[:NB] - 1
    bmax = (np.maximum.reduceat(deltas, bstart) if NB
            else np.zeros(0, np.int64))
    # exact bit_length via the frexp exponent (x = m * 2**e, 0.5<=m<1)
    _, exp = np.frexp(np.maximum(bmax, 1).astype(np.float64))
    width = np.clip(exp.astype(np.int64), 1, max_bits)
    bits_arr = width.astype(np.int32)
    base_arr = prev[bstart].astype(np.int32)
    count_arr = (bend - bstart + 1).astype(np.int32)
    min_arr = docs[bstart].astype(np.int32)
    max_arr = docs[bend].astype(np.int32)
    tf_arr = np.zeros((NB, block), dtype=np.float16)
    tf_arr[blk, lane] = h.tfs[src]
    words_blk = (block * width + 31) // 32
    words_per_block = int(words_blk.max()) if NB else 1
    lanes = lane_width(words_per_block)
    bitpos = lane * width[blk]
    wi, off = bitpos >> 5, (bitpos & 31).astype(np.uint64)
    dv = deltas.astype(np.uint64)
    flat = np.zeros(NB * lanes, dtype=np.uint64)
    at = blk * lanes + wi
    np.bitwise_or.at(flat, at, (dv << off) & np.uint64(0xFFFFFFFF))
    spill = np.where(off > 0, dv >> (np.uint64(32) - off), np.uint64(0))
    keep = (spill != 0) & (wi + 1 < words_blk[blk])
    np.bitwise_or.at(flat, at[keep] + 1, spill[keep])
    packed = flat.reshape(NB, lanes).astype(np.uint32)
    tfirst, tcount = _block_tile_routing(min_arr, max_arr, h.num_docs,
                                         route_tile)
    return PackedCsrIndex(
        sorted_hash=jnp.asarray(h.term_hashes[order].astype(np.uint32)),
        df=jnp.asarray(h.df[order].astype(np.int32)),
        block_offsets=jnp.asarray(block_offsets.astype(np.int32)),
        block_bits=jnp.asarray(bits_arr), block_base=jnp.asarray(base_arr),
        block_count=jnp.asarray(count_arr), packed=jnp.asarray(packed),
        tf_pairs=jnp.asarray(pair_tf_rows(tf_arr)),
        docs=DocTable(norm=jnp.asarray(h.norm), rank=jnp.asarray(h.rank)),
        max_posting_len=h.max_posting_len,
        words_per_block=words_per_block,
        block=block,
        block_min=jnp.asarray(min_arr), block_max=jnp.asarray(max_arr),
        tile_first=jnp.asarray(tfirst), tile_count=jnp.asarray(tcount),
        route_tile=int(route_tile),
        route_pairs_max=int(tcount.sum()),
        route_span_max=int(tcount.max()) if len(tcount) else 0,
    )


# ---------------------------------------------------------------------------
# (beyond paper) BandedCsrIndex — per-term-band layout choice
# ---------------------------------------------------------------------------


def term_packed_words(h: PostingsHost, block: int = BLOCK,
                      max_bits: int = 32
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-term packed width: the int32 words the WIDEST block of each
    term would occupy under ``build_packed_csr``'s delta+bit-packing,
    plus the term's block count.  Returned in ``h``'s original term
    order (i64[W], i64[W]); terms with no postings get width 0.

    This is the byte model's view of the uniform-stride problem: a
    monolithic ``PackedCsrIndex`` stores every block at
    ``max(words)`` — one rare term with 16-bit deltas inflates the
    stride of every dense term in the segment.  ``build_banded`` uses
    these widths to cut the vocabulary into a packed band (width <=
    cut) and an HOR tail.

    The widths replicate the builder exactly: per-block max delta
    (first delta of a block is taken against the previous block's last
    doc id, ``-1`` at term start), bit width via the float exponent
    (``np.frexp`` — exact ``int.bit_length`` for integers below 2**53,
    unlike a log2-plus-epsilon nudge which misrounds near 2**31),
    clipped to [1, max_bits], then ``(block*bits + 31) // 32`` words.
    """
    W = h.num_terms
    lengths = np.diff(h.offsets).astype(np.int64)
    has = lengths > 0
    nblocks = np.maximum(-(-lengths // block), has.astype(np.int64))
    words = np.zeros(W, dtype=np.int64)
    P = h.num_postings
    if P == 0 or W == 0:
        return words, nblocks
    docs = h.doc_ids.astype(np.int64)
    prev = np.empty(P, dtype=np.int64)
    prev[1:] = docs[:-1]
    prev[h.offsets[:-1][has]] = -1          # term starts restart the delta
    deltas = docs - prev
    block_offsets = np.zeros(W + 1, dtype=np.int64)
    np.cumsum(nblocks, out=block_offsets[1:])
    NB = int(block_offsets[-1])
    # posting-array position where each block starts: term slab start +
    # within-term block index * block
    bstart = (np.repeat(h.offsets[:-1][has], nblocks[has]).astype(np.int64)
              + (np.arange(NB, dtype=np.int64)
                 - np.repeat(block_offsets[:-1][has], nblocks[has])) * block)
    bmax = np.maximum.reduceat(deltas, bstart)
    # exact bit_length via the frexp exponent (x = m * 2**e, 0.5<=m<1)
    _, exp = np.frexp(np.maximum(bmax, 1).astype(np.float64))
    bits = np.clip(exp.astype(np.int64), 1, max_bits)
    w_blk = (block * bits + 31) // 32
    term_of_block = np.repeat(np.arange(W, dtype=np.int64), nblocks)
    np.maximum.at(words, term_of_block, w_blk)
    return words, nblocks


@dataclasses.dataclass(frozen=True)
class BandedCsrIndex:
    """Per-term-band sealed segment: packed band + HOR tail.

    Terms whose widest packed block fits in ``<= cut`` int32 words go
    into a ``PackedCsrIndex`` with a BAND-LOCAL ``words_per_block``
    (the dense, high-df shape packing wants); the rest — the
    decode-bound df≈1 tail whose 16+-bit deltas would inflate the
    uniform stride — stay in a ``BlockedIndex``.  Both bands are
    FULL-vocabulary sub-indexes over the SAME doc space (a term's
    postings live in exactly one band; the other band holds an empty
    block range for it), share one ``DocTable``, and share the
    ``sorted_hash`` buffer — one term lookup serves both bands, and a
    query's score is the sum of the two band partials.

    The band cut itself is HOST metadata (``Segment.band_cut``), not a
    pytree static: it varies per segment, and a non-quantized static
    here would defeat the per-(size_class, layout) scorer memoization.
    """
    _static_fields = ()
    packed: PackedCsrIndex
    hor: BlockedIndex

    @property
    def docs(self) -> DocTable:
        return self.packed.docs

    @property
    def sorted_hash(self) -> Array:
        return self.packed.sorted_hash

    @property
    def df(self) -> Array:
        return self.packed.df + self.hor.df

    @property
    def num_terms(self) -> int:
        return self.packed.num_terms

    @property
    def block(self) -> int:
        return self.packed.block

    @property
    def route_tile(self) -> int:
        return self.packed.route_tile

    @property
    def max_posting_len(self) -> int:
        return max(self.packed.max_posting_len, self.hor.max_posting_len)

    def lookup_terms(self, hashes: Array) -> Array:
        return self.packed.lookup_terms(hashes)

    def term_df(self, term_ids: Array) -> Array:
        return self.packed.term_df(term_ids) + self.hor.term_df(term_ids)

    def gather_postings(self, term_ids: Array, cap: int
                        ) -> Tuple[Array, Array, Array]:
        # a term's postings live in exactly one band; the other band
        # yields inert fill (-1 / 0.0 / False), so the merge is a
        # lane-wise max / sum / or
        dp, tp, vp = self.packed.gather_postings(term_ids, cap)
        dh, th, vh = self.hor.gather_postings(term_ids, cap)
        return jnp.maximum(dp, dh), tp + th, vp | vh

    def nbytes(self) -> int:
        # the DocTable is shared between the bands — count it once
        return (self.packed.nbytes() + self.hor.nbytes()
                - self.docs.nbytes())

    def posting_bytes(self) -> int:
        return int(self.packed.posting_bytes() + self.hor.posting_bytes())


_register(BandedCsrIndex)


def _band_host(h: PostingsHost, keep: np.ndarray) -> PostingsHost:
    """Full-vocabulary sub-host: terms outside ``keep`` stay in the
    vocabulary with df 0 and an empty posting slab, so both bands'
    hash-sorted term ids stay aligned."""
    lengths = np.diff(h.offsets).astype(np.int64)
    kept = np.where(keep, lengths, 0)
    offsets = np.zeros(h.num_terms + 1, dtype=np.int64)
    np.cumsum(kept, out=offsets[1:])
    mask = np.repeat(keep, lengths)
    return PostingsHost(
        term_hashes=h.term_hashes,
        df=np.where(keep, h.df, 0).astype(h.df.dtype),
        offsets=offsets,
        doc_ids=h.doc_ids[mask],
        tfs=h.tfs[mask],
        num_docs=h.num_docs,
        norm=h.norm,
        rank=h.rank,
    )


def build_banded(h: PostingsHost, max_band_words: int | None = None,
                 block: int = BLOCK, route_tile: int = ROUTE_TILE,
                 lane_quantum: int = 1) -> BandedCsrIndex:
    """Build a banded segment.  ``max_band_words`` (the band cut, in
    int32 words) defaults to the byte-model optimum from
    ``size_model.choose_band_cut``; pass the recorded cut explicitly to
    reproduce a build bitwise (snapshot restore).  ``lane_quantum``
    lets the seal path price the cut at the packed lane-dim quantum it
    will pad to (8), so the chooser sees seal-time bytes."""
    words, nblocks = term_packed_words(h, block=block)
    if max_band_words is None:
        from repro.core import size_model
        cut, _ = size_model.choose_band_cut(words, nblocks, block=block,
                                            lane_quantum=lane_quantum)
    else:
        cut = int(max_band_words)
    in_packed = (words > 0) & (words <= cut)
    packed = build_packed_csr(_band_host(h, in_packed), block=block,
                              route_tile=route_tile)
    hor = build_blocked(_band_host(h, ~in_packed), block=block,
                        route_tile=route_tile)
    # share the DocTable and the (identical-content) sorted_hash buffer
    hor = dataclasses.replace(hor, docs=packed.docs,
                              sorted_hash=packed.sorted_hash)
    return BandedCsrIndex(packed=packed, hor=hor)


REPRESENTATIONS = {
    "pr": build_coo,            # Plain-Relational
    "or": build_csr,            # Object-Relational
    "cor": build_compact_csr,   # Compact Object-Relational
    "hor": build_blocked,       # HStore Object-Relational
    "packed": build_packed_csr,  # beyond-paper
    "banded": build_banded,      # beyond-paper: per-term-band choice
}
