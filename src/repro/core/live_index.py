"""Segmented live index — LSM-style ingest, tombstone deletes, and
multi-segment fused query over the paper's representations.

The paper's §3.6 maintenance story stops at batch re-indexing: drop the
derived structures, merge-sort every posting, rebuild.  That is
O(total postings) of work and a device-shape change (new XLA
compilation) per ingest batch.  This module replaces it with the
structure every production DB-IR engine converges on (ODYS,
arXiv:1208.4270; compressed-index maintenance, arXiv:1209.5448):
immutable sealed runs + a small mutable tail + background
reorganization.

Segment lifecycle (delta -> seal -> compact)
--------------------------------------------

  * DELTA — a fixed-capacity, append-only, doc-major postings buffer
    (uncompressed CSR).  Ingest batches append here in O(batch) time;
    the device mirror has STATIC shapes (capacity-padded), so queries
    over the delta never recompile.  Postings are kept per-doc in
    ascending unified-term order — the same per-document accumulation
    order the bulk builder's term-major sort produces, which is what
    keeps recomputed norms bit-identical to a from-scratch rebuild.

  * SEAL — when the delta fills (or ``seal()`` is called), its contents
    become one immutable sealed segment: a ``BlockedIndex`` built by the
    existing bulk path over the segment's contiguous doc-id range, then
    padded to a static SIZE CLASS (geometric shape quantization:
    ``layouts.size_class`` / ``pad_blocked_to_class``).

  * COMPACT — a size-tiered policy (core/compaction.py) merges the
    newest run of similarly-sized segments into one, physically dropping
    tombstoned postings and re-blocking.  Doc ids are NEVER reused or
    renumbered, so merged ranges stay contiguous and external references
    stay valid.  ``compact()`` is synchronous but background-callable:
    queries between compactions read the old stack unchanged.

Recompile-avoidance contract
----------------------------

Every per-segment scorer (kernels/ops.py ``fused_segment_topk`` et al.)
is a module-level jitted function taking the segment as a pytree
ARGUMENT; its compilation is keyed on the segment's size class, not its
identity.  Sealing quantizes all shape-bearing statics (block count,
vocab width, doc span, routing budgets, posting-length bounds) to a few
geometric classes, so after one warmup per class, sealing and querying
new segments triggers ZERO new XLA compilations — asserted by the churn
test via jit-cache counters (``scorer_cache_sizes``).  The cross-segment
candidate merge runs on the host (numpy), so a changing segment count
never enters a jit signature.

Exact-ranking contract
----------------------

Scoring state that depends on the WHOLE corpus is maintained globally
and exactly: ``df`` over live documents (incremented on add,
decremented on delete using the per-doc forward postings), the live doc
count behind idf, every document's token count and the live token
total behind BM25's average length, and the ranking's per-document rows
(``core/ranking.py``: tf-idf norms with the same float64 op sequence as
the bulk builder, or BM25's length normalisation) recomputed per
mutation batch.  Tombstones mask deleted docs by zeroing their row —
the deleted-doc path of every engine, applied inside the fused kernel's
doc-metadata tail.  The result: at ANY point of an add/delete/compact
schedule, top-k from the fused candidates engine is bit-identical (ties
included) to the jnp oracle over ``bulk_build`` of the equivalent live
corpus
(``export_live_corpus`` builds exactly that corpus for the parity
tests; ranking parity needs ``rank_blend == 0`` or an oracle sharing
this index's static-rank table, and the default full-list ``cap``).

Posting-merge work (the ``stats`` counters): each posting is appended
once (an O(1) buffer write), sealed once, and compacted
O(log N / log min_run) times — vs the rebuild path re-sorting EVERY
posting EVERY batch.  Norm refresh is a separate vectorized
O(live postings) bincount per mutation batch (counted apart in
``postings_norm_refreshed``; it is metadata maintenance, not index
merge work, and never re-sorts or rebuilds posting structures).

Epochs and pinned views (the serving-tier hook)
-----------------------------------------------

Every query-visible mutation (add, delete, seal, compact) advances a
monotonic ``epoch`` counter; ``view()`` returns an immutable
``LiveView`` pinned to the current epoch — shallow-pinned segment
indexes (segment replacement never mutates the old pytree), the delta's
device mirror (rebuilt, never mutated, on change), and copies of the
in-place-mutated global state (df, live mask).  A pinned view answers
``topk``/``conjunctive`` bit-identically to the live index AT THAT
EPOCH no matter what lands afterwards, which is what lets the serving
tier (``repro/serve``) micro-batch queries against a consistent index
while ingest and background maintenance run.  ``view()`` itself must be
called serially with writers (the serving tier holds a write lock for
the pin, never for the query).
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import build as build_mod
from repro.core import compaction, layouts, size_model
from repro.core.build import TokenizedCorpus
from repro.core.layouts import DocTable, PostingsHost
from repro.core.query import QueryResult, final_scores
from repro.core.ranking import TFIDF_COSINE, Ranking
from repro.distributed.topk import merge_topk_candidates_host
from repro.kernels import autotune, ops
from repro.obs.registry import EventLog
from repro.obs.trace import stage
from repro.kernels.fused_decode_score import (TILE, default_k_tile,
                                              extract_tile_candidates)

Array = jax.Array


# ---------------------------------------------------------------------------
# module-level jitted helpers (argument-passed state => stable caches)
# ---------------------------------------------------------------------------


def query_weights(df: np.ndarray, live_docs: int):
    """tf-idf cosine's host query weights (``Ranking.query_weights``):
    every server (single host, every shard of a mesh) takes its weights
    from the ranking on the host, so a query scores with the same bits
    whatever batch shape or program it rides in.  Returns
    (idf f32[..., T], query norms f32[...])."""
    return TFIDF_COSINE.query_weights(df, live_docs)


@functools.partial(jax.jit, static_argnames=("k_tile", "tile", "rank_blend",
                                             "ranking"))
def _delta_candidates(terms: Array, tfs: Array, doc_of: Array, row: Array,
                      rank: Array, tids: Array, idf_w: Array, qscale: Array,
                      doc_base: Array, *, k_tile: int, tile: int = TILE,
                      rank_blend: float = 0.0,
                      ranking: Ranking = TFIDF_COSINE):
    """Score the mutable delta (capacity-padded doc-major postings) and
    reduce to the same per-tile candidate lists the sealed-segment
    kernels emit.  All shapes are delta capacities — static for the
    index's lifetime."""
    dcap = row.shape[0]
    with jax.named_scope("delta_scan"):
        # per-posting query weight: each posting's unified term id
        # against the query's (dedup'd) term-id slots
        match = ((terms[None, :, None] == tids[:, None, :]) &
                 (tids[:, None, :] >= 0) & (terms[None, :, None] >= 0))
        w_p = jnp.sum(jnp.where(match, idf_w[:, None, :], 0.0), axis=2)
        valid = doc_of >= 0
        safe_d = jnp.where(valid, doc_of, dcap)
        prow = (row[jnp.where(valid, doc_of, 0)] if ranking.saturates
                else None)
        contrib = jnp.where(valid[None, :],
                            ranking.posting(tfs, prow)[None, :] * w_p, 0.0)

        def row_scores(c):
            acc = jnp.zeros((dcap + 1,), jnp.float32).at[safe_d].add(
                c, mode="drop")
            return acc[:dcap]

        scores = jax.vmap(row_scores)(contrib)
        final = final_scores(scores, row, rank, qscale, rank_blend, ranking)
        vals, ids = extract_tile_candidates(final, tile, k_tile)
        gids = jnp.where(ids >= 0, ids + doc_base, -1)
    return vals, gids


@functools.partial(jax.jit, static_argnames=("k_tile", "tile"))
def _delta_conjunctive(terms: Array, tfs: Array, doc_of: Array, norm: Array,
                       tids: Array, idf_w: Array, needed: Array,
                       doc_base: Array, *, k_tile: int, tile: int = TILE):
    """AND-semantics counts + scores over the delta for ONE query.  The
    delta is scanned in full (no posting cap), so it never truncates —
    its ``truncated_terms`` contribution is always zero."""
    dcap = norm.shape[0]
    match = ((terms[:, None] == tids[None, :]) & (tids[None, :] >= 0) &
             (terms[:, None] >= 0))
    w_p = jnp.sum(jnp.where(match, idf_w[None, :], 0.0), axis=1)
    hit_p = jnp.any(match, axis=1)
    valid = doc_of >= 0
    safe_d = jnp.where(valid, doc_of, dcap)
    scores = jnp.zeros((dcap + 1,), jnp.float32).at[safe_d].add(
        jnp.where(valid, tfs * w_p, 0.0), mode="drop")[:dcap]
    counts = jnp.zeros((dcap + 1,), jnp.int32).at[safe_d].add(
        jnp.where(valid & hit_p, 1, 0).astype(jnp.int32),
        mode="drop")[:dcap]
    ok = counts >= needed
    final = jnp.where(ok & (norm > 0),
                      scores / jnp.maximum(norm, 1e-12), -jnp.inf)
    vals, ids = extract_tile_candidates(final[None], tile, k_tile)
    gids = jnp.where(ids[0] >= 0, ids[0] + doc_base, -1)
    return vals[0], gids


def scorer_cache_sizes() -> dict:
    """jit-cache entry counts for every compiled piece of the live query
    path.  The churn test snapshots this after warmup and asserts zero
    growth across further seals, compactions, and queries — the
    measurable form of the recompile-avoidance contract."""
    sizes = dict(ops.segment_scorer_cache_sizes())
    sizes.update({
        "delta_candidates": _delta_candidates._cache_size(),
        "delta_conjunctive": _delta_conjunctive._cache_size(),
    })
    return sizes


def _dedup_np(qh: np.ndarray) -> np.ndarray:
    """Host twin of ``query.dedup_query_hashes`` (keep first, zero rest)."""
    out = qh.copy()
    t = qh.shape[-1]
    eq = qh[..., :, None] == qh[..., None, :]
    earlier = np.tril(np.ones((t, t), bool), k=-1)
    dup = (eq & earlier).any(axis=-1) & (qh != 0)
    out[dup] = 0
    return out


def _lookup_sorted(hash_sorted: np.ndarray, hash_order: np.ndarray,
                   qh: np.ndarray) -> np.ndarray:
    """u32[...] hashes -> unified term ids (i64, -1 absent/empty) via a
    host binary search over the sorted vocabulary."""
    w = len(hash_sorted)
    if w == 0:
        return np.full(qh.shape, -1, np.int64)
    flat = qh.reshape(-1)
    pos = np.searchsorted(hash_sorted, flat)
    posc = np.minimum(pos, w - 1)
    hit = (hash_sorted[posc] == flat) & (flat != 0)
    return np.where(hit, hash_order[posc], -1).reshape(qh.shape)


# ---------------------------------------------------------------------------
# stats / delta / segment containers
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LiveIndexStats:
    """Work and lifecycle counters (all cumulative).

    ``postings_merged`` is the posting-MERGE work (postings touched by
    sort/merge/rebuild operations): seal builds + compaction merges —
    each posting is sealed once and compacted O(log N / log min_run)
    times.  Delta appends are pure O(1) buffer writes (no sort, no
    structure rebuild) and are counted apart in ``postings_appended``,
    as is the vectorized per-mutation norm refresh.  The rebuild path's
    equivalent is its full re-sort: EVERY posting touched, every batch.
    """
    postings_appended: int = 0      # delta appends (O(1)/posting writes)
    postings_sealed: int = 0        # delta -> segment bulk builds
    postings_compacted: int = 0     # compaction merge inputs
    postings_norm_refreshed: int = 0  # vectorized norm recompute (not merge)
    docs_added: int = 0
    seals: int = 0
    compactions: int = 0
    deletes: int = 0
    layout_rewrites: int = 0        # single-segment layout conversions

    @property
    def postings_merged(self) -> int:
        return self.postings_sealed + self.postings_compacted


class _Delta:
    """Fixed-capacity append-only doc-major postings buffer (host side).

    Capacities are static so the device mirror's shapes never change;
    per-doc postings are stored in ascending unified-term order."""

    def __init__(self, doc_cap: int, post_cap: int, doc_base: int):
        self.doc_cap = int(doc_cap)
        self.post_cap = int(post_cap)
        self.doc_base = int(doc_base)
        self.n_docs = 0
        self.n_postings = 0
        self.terms = np.full(self.post_cap, -1, np.int32)
        self.tfs = np.zeros(self.post_cap, np.float32)
        self.doc_of = np.full(self.post_cap, -1, np.int32)
        self.doc_offsets = np.zeros(self.doc_cap + 1, np.int64)

    def append(self, lens: np.ndarray, terms: np.ndarray,
               tfs: np.ndarray) -> None:
        n, p = len(lens), len(terms)
        assert self.n_docs + n <= self.doc_cap
        assert self.n_postings + p <= self.post_cap
        s = self.n_postings
        self.terms[s:s + p] = terms
        self.tfs[s:s + p] = tfs
        self.doc_of[s:s + p] = np.repeat(
            np.arange(self.n_docs, self.n_docs + n, dtype=np.int32),
            lens)
        off = self.doc_offsets
        off[self.n_docs + 1:self.n_docs + n + 1] = \
            off[self.n_docs] + np.cumsum(lens)
        self.n_docs += n
        self.n_postings += p


@dataclasses.dataclass
class Segment:
    """One immutable sealed run.

    ``index`` is a size-class-padded BlockedIndex over LOCAL doc ids
    (global id = local + doc_base); the host arrays are the (doc, term)-
    sorted forward canonical used for norm refresh, per-doc delete
    lookups, and compaction merges."""
    index: (layouts.BlockedIndex | layouts.PackedCsrIndex
            | layouts.BandedCsrIndex)
    doc_base: int
    doc_span: int              # allocated local id range (may have holes)
    doc_of: np.ndarray         # i32[P] local doc ids, doc-major
    terms: np.ndarray          # i32[P] unified term ids, asc within doc
    tfs: np.ndarray            # f32[P]
    doc_offsets: np.ndarray    # i64[doc_span + 1] forward CSR
    n_postings: int
    size_class: int = 0        # padded doc-span class the build used
    num_terms: int = 0         # distinct terms with postings in this run
    chooser_reason: str = "default"  # how the layout ladder resolved
    band_cut: int = 0          # banded only: packed-band width cut (words)

    @property
    def layout(self) -> str:
        """The sealed layout this segment was built with — ``"hor"``,
        ``"packed"``, or ``"banded"``.  Snapshots record it per segment
        so a mixed-layout stack restores each segment in its ORIGINAL
        layout (bitwise round-trip), and the sharded stack groups on
        it."""
        if isinstance(self.index, layouts.BandedCsrIndex):
            return "banded"
        return ("packed" if isinstance(self.index, layouts.PackedCsrIndex)
                else "hor")

    @property
    def stats(self) -> size_model.SegmentStats:
        """Aggregate shape the layout chooser sees for this run."""
        return size_model.SegmentStats(num_docs=self.doc_span,
                                       num_postings=self.n_postings,
                                       num_terms=self.num_terms)


def _with_docs(ix, docs: DocTable):
    """The segment index ``ix`` with its DocTable replaced (a banded
    segment's two bands share one DocTable, as at build)."""
    if isinstance(ix, layouts.BandedCsrIndex):
        return layouts.BandedCsrIndex(
            packed=dataclasses.replace(ix.packed, docs=docs),
            hor=dataclasses.replace(ix.hor, docs=docs))
    return dataclasses.replace(ix, docs=docs)


def _layout_mix(segments) -> dict:
    """Aggregate per-layout composition of a sealed stack — the
    observability payload behind ``SegmentedIndex.layout_mix`` /
    ``LiveView.layout_mix`` and ``ServerMetrics.layout_mix``."""
    mix = {"segments": [], "counts": {}, "docs": {}, "postings": {},
           "reasons": {}}
    for seg in segments:
        lay = seg.layout
        rec = {
            "doc_base": int(seg.doc_base), "doc_span": int(seg.doc_span),
            "size_class": int(seg.size_class), "layout": lay,
            "n_postings": int(seg.n_postings),
            "chooser_reason": seg.chooser_reason}
        if lay == "banded":
            rec["band_cut"] = int(seg.band_cut)
        mix["segments"].append(rec)
        mix["counts"][lay] = mix["counts"].get(lay, 0) + 1
        mix["docs"][lay] = mix["docs"].get(lay, 0) + int(seg.doc_span)
        mix["postings"][lay] = (mix["postings"].get(lay, 0)
                                + int(seg.n_postings))
        mix["reasons"][seg.chooser_reason] = \
            mix["reasons"].get(seg.chooser_reason, 0) + 1
    return mix


# ---------------------------------------------------------------------------
# epoch-pinned immutable view (the serving tier's unit of consistency)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LiveView:
    """An immutable snapshot of the query-visible index state at one
    epoch.

    Pinning is cheap: sealed segment indexes are immutable pytrees
    (compaction and norm refresh REPLACE them, never mutate), the
    delta's device mirror is rebuilt — not mutated — on change, and only
    the in-place-mutated host state (df, live mask, delta tail) is
    copied.  A view answers ``topk``/``conjunctive`` exactly as the
    ``SegmentedIndex`` did at pin time, and ``export_live_corpus``
    produces the matching oracle corpus — so a response served from a
    pinned view can be checked bit-identical against the jnp oracle OF
    ITS EPOCH even while writers churn the live index.
    """
    epoch: int
    segments: tuple            # pinned shallow copies of Segment
    delta_dev: dict            # capacity-padded device arrays
    delta_terms: np.ndarray    # host delta tail, trimmed copies
    delta_tfs: np.ndarray
    delta_doc_of: np.ndarray
    delta_doc_offsets: np.ndarray   # i64[delta_n_docs + 1]
    delta_doc_base: int
    delta_n_docs: int
    hashes: np.ndarray         # unified vocabulary (replaced on growth)
    hash_sorted: np.ndarray
    hash_order: np.ndarray
    df: np.ndarray             # i64[W] live global df (copy)
    live: np.ndarray           # bool[num_docs] (copy)
    live_docs: int
    num_docs: int
    live_tokens: int = 0       # token count of the live docs
    ranking: Ranking = TFIDF_COSINE

    @property
    def num_segments(self) -> int:
        return len(self.segments)

    @property
    def avgdl(self) -> float:
        """Average live document length in tokens (BM25's avgdl)."""
        return self.live_tokens / self.live_docs if self.live_docs else 0.0

    def layout_mix(self) -> dict:
        """Per-layout composition of the pinned stack (counts, docs,
        postings, chooser reasons, per-segment decisions)."""
        return _layout_mix(self.segments)

    # -- query path (identical op sequence to the live index) --------------

    def _prep(self, qh: np.ndarray):
        qh = _dedup_np(np.asarray(qh, np.uint32))
        tids = _lookup_sorted(self.hash_sorted, self.hash_order, qh)
        if len(self.df):
            df = np.where(tids >= 0, self.df[np.maximum(tids, 0)],
                          0).astype(np.int32)
        else:
            df = np.zeros(qh.shape, np.int32)
        w, qscale = self.ranking.query_weights(df, self.live_docs)
        return qh, tids, jnp.asarray(w), jnp.asarray(qscale)

    def topk(self, query_hashes, k: int, *, cap: int | None = None,
             rank_blend: float = 0.0, engine: str = "pallas",
             mode: str = "candidates", backend: str = "pallas",
             return_stats: bool = False, tune=None, trace=None):
        """Batched top-k over this view's delta + sealed segments — the
        same contract as ``SegmentedIndex.topk``, evaluated against the
        pinned epoch.

        ``trace`` optionally takes a ``repro.obs.Trace``: each sealed
        segment records a child span of ``"score"`` carrying its size
        class, layout, the TuneConfig geometry the dispatch resolved,
        and the analytic candidate / posting byte costs; the delta and
        the host candidate merge record their own children.  Tracing
        adds host-side timing only — the op sequence, and therefore
        every result bit, is identical with ``trace=None``.

        Kernel geometry resolves PER SEGMENT from the active tuning
        table (``tune`` overrides it for every segment): each sealed
        segment's (backend, size_class, layout) picks its own tile
        width / reducer / candidate count, so a view mixing a 4k-doc
        segment and a 512k-doc segment runs each at its tuned shape.
        The delta always scores at the default tile (its buffers are
        capacity-padded, not size-classed) with ``k_tile`` clamped to
        that tile — exactness only needs ``k_tile >= min(k, tile)`` per
        SOURCE, and the host merge accepts ragged widths."""
        if engine not in ("pallas", "jnp"):
            raise ValueError(f"unknown engine: {engine!r}")
        if mode not in ("candidates", "dense"):
            raise ValueError(f"unknown fused-engine mode: {mode!r}")
        qh = np.asarray(query_hashes, np.uint32)
        if qh.ndim != 2:
            raise ValueError("query_hashes must be [B, T]")
        ranking = self.ranking
        if ranking.saturates and (mode == "dense" or any(
                s.layout == "banded" for s in self.segments)):
            raise NotImplementedError(
                f"{ranking.name} is served by the candidates engine over "
                "hor and packed segments; dense mode and banded segments "
                "score tf-idf cosine only")
        with stage(trace, "dispatch", parent="score"):
            qh, tids, idf_w, qscale = self._prep(qh)
            qh_dev = jnp.asarray(qh)
            k_tile = default_k_tile(k)        # delta path: TILE-wide tiles
            vals, ids, overflows = [], [], []
            for seg in self.segments:
                cfg = (tune if tune is not None else autotune.lookup(
                    backend, int(seg.index.docs.num_docs), seg.layout))
                seg_kt = cfg.resolve_k_tile(k)
                c = int(cap) if cap is not None else seg.index.max_posting_len
                if seg.layout == "banded":
                    mp_p, mp_h, cap_p, cap_h = ops.banded_pairs_budgets(
                        seg.index, *qh.shape, c, cfg.tile)
                    mp = mp_p + mp_h
                else:
                    mp = ops.default_max_pairs(seg.index, *qh.shape, c,
                                               cfg.tile)
                b = jnp.asarray(np.int32(seg.doc_base))
                span = None
                if trace is not None:
                    span = trace.span(
                        "segment", parent="score", doc_base=int(seg.doc_base),
                        size_class=int(seg.size_class), layout=seg.layout,
                        tile=int(cfg.tile), k_tile=int(seg_kt),
                        reducer=cfg.reducer, max_pairs=int(mp),
                        candidate_bytes=size_model.candidate_bytes_per_query(
                            int(seg.index.docs.num_docs), int(cfg.tile),
                            int(seg_kt)),
                        posting_bytes=size_model.est_posting_bytes(
                            seg.stats, seg.layout),
                        **({"band_cut": int(seg.band_cut)}
                           if seg.layout == "banded" else {}))
                if engine == "jnp":
                    v, g, o = ops.jnp_segment_topk(
                        seg.index, qh_dev, idf_w, qscale, b, k_tile=k_tile,
                        cap=c, rank_blend=rank_blend, ranking=ranking)
                elif seg.layout == "banded":
                    # one fused dense launch per band, partials summed in
                    # the engine; both "candidates" and "dense" modes route
                    # here (a per-band candidate top-k cannot merge — scores
                    # are additive over terms, not max-mergeable)
                    v, g, o = ops.fused_segment_banded_topk(
                        seg.index, qh_dev, idf_w, qscale, b, k_tile=seg_kt,
                        cap_packed=cap_p, cap_hor=cap_h,
                        max_pairs_packed=mp_p, max_pairs_hor=mp_h,
                        rank_blend=rank_blend, tile=cfg.tile,
                        backend=backend, q_pad=cfg.q_pad)
                elif mode == "dense":
                    v, g, o = ops.fused_segment_dense_topk(
                        seg.index, qh_dev, idf_w, qscale, b, k_tile=seg_kt,
                        cap=c, max_pairs=mp, rank_blend=rank_blend,
                        tile=cfg.tile, backend=backend, q_pad=cfg.q_pad)
                else:
                    v, g, o = ops.fused_segment_topk(
                        seg.index, qh_dev, idf_w, qscale, b, k_tile=seg_kt,
                        cap=c, max_pairs=mp, rank_blend=rank_blend,
                        tile=cfg.tile, backend=backend, q_pad=cfg.q_pad,
                        reducer=cfg.reducer, ranking=ranking)
                # keep device arrays until every segment is dispatched —
                # transferring here would serialize the per-segment launches
                vals.append(v)
                ids.append(g)
                overflows.append(o)
                if span is not None:
                    # dispatch only: the device is waited for later
                    span.end()
            dspan = (trace.span("delta", parent="score",
                                postings=int(self.delta_terms.shape[0]),
                                docs=int(self.delta_n_docs),
                                k_tile=int(k_tile))
                     if trace is not None else None)
            dev = self.delta_dev
            dv, dg = _delta_candidates(
                dev["terms"], dev["tfs"], dev["doc_of"], dev["row"],
                dev["rank"], jnp.asarray(tids.astype(np.int32)), idf_w,
                qscale, jnp.asarray(np.int32(self.delta_doc_base)),
                k_tile=k_tile, rank_blend=rank_blend, ranking=ranking)
            vals.append(dv)
            ids.append(dg)
            if dspan is not None:
                dspan.end()
        with stage(trace, "device_wait", parent="score"):
            # the one sync point: every segment's and the delta's
            # results are on the device before the merge reads them
            jax.block_until_ready((overflows, dv, dg))
            overflow = sum(int(o) for o in overflows)
            if not return_stats:
                # stats callers inspect the counter themselves; everyone
                # else gets the engines' loud-overflow contract
                ops.warn_on_overflow(overflow, "live-view fused engine")
        mv, mi = merge_topk_candidates_host(vals, ids, k, trace=trace)
        with stage(trace, "result", parent="score"):
            hit = np.isfinite(mv)
            result = QueryResult(
                doc_ids=jnp.asarray(np.where(hit, mi, -1).astype(np.int32)),
                scores=jnp.asarray(np.where(hit, mv, 0.0).astype(
                    np.float32)))
        if return_stats:
            return result, {"pair_overflow": overflow}
        return result

    def conjunctive(self, query_hashes, k: int, cap: int):
        """AND semantics over the pinned index for ONE query [T]; see
        ``SegmentedIndex.conjunctive`` for the stats contract.  tf-idf
        cosine only."""
        if self.ranking.saturates:
            raise NotImplementedError(
                f"conjunctive queries score tf-idf cosine only, not "
                f"{self.ranking.name}")
        qh = _dedup_np(np.asarray(query_hashes, np.uint32).reshape(1, -1))
        needed = int((qh != 0).sum())
        qh1, tids, idf_w, _ = self._prep(qh)
        qh_dev = jnp.asarray(qh1[0])
        k_tile = default_k_tile(k)
        vals, ids, truncs = [], [], []
        for seg in self.segments:
            v, g, t = ops.jnp_segment_conjunctive(
                seg.index, qh_dev, idf_w[0], jnp.asarray(np.int32(needed)),
                jnp.asarray(np.int32(seg.doc_base)), k_tile=k_tile,
                cap=int(cap))
            vals.append(v)
            ids.append(g)
            truncs.append(t)
        truncated = sum(int(t) for t in truncs)
        ops.record_truncated(truncated)
        dev = self.delta_dev
        dv, dg = _delta_conjunctive(
            dev["terms"], dev["tfs"], dev["doc_of"], dev["row"],
            jnp.asarray(tids[0].astype(np.int32)), idf_w[0],
            jnp.asarray(np.int32(needed)),
            jnp.asarray(np.int32(self.delta_doc_base)), k_tile=k_tile)
        vals.append(np.asarray(dv))
        ids.append(np.asarray(dg))
        mv, mi = merge_topk_candidates_host(vals, ids, k)
        hit = np.isfinite(mv)
        result = QueryResult(
            doc_ids=jnp.asarray(np.where(hit, mi, -1).astype(np.int32)),
            scores=jnp.asarray(np.where(hit, mv, 0.0).astype(np.float32)))
        return result, {"truncated_terms": truncated}

    # -- oracle support -----------------------------------------------------

    def _owner(self, d: int):
        """Segment position owning global doc id d (None = the delta)."""
        if d >= self.delta_doc_base:
            return None
        bases = [s.doc_base for s in self.segments]
        i = bisect.bisect_right(bases, d) - 1
        seg = self.segments[i]
        assert seg.doc_base <= d < seg.doc_base + seg.doc_span
        return i

    def export_live_corpus(self):
        """The equivalent live corpus AT THIS EPOCH over the pinned
        vocabulary, plus the ascending global ids of its docs — exactly
        what a parity oracle should ``bulk_build`` against this view."""
        live_ids = np.flatnonzero(self.live)
        doc_term_ids, doc_counts = [], []
        for d in live_ids:
            o = self._owner(int(d))
            if o is None:
                local = int(d) - self.delta_doc_base
                if local >= self.delta_n_docs:
                    t = np.zeros(0, np.int64)
                    tf = np.zeros(0, np.float64)
                else:
                    a, b = (self.delta_doc_offsets[local],
                            self.delta_doc_offsets[local + 1])
                    t = self.delta_terms[a:b]
                    tf = self.delta_tfs[a:b]
            else:
                seg = self.segments[o]
                local = int(d) - seg.doc_base
                a, b = seg.doc_offsets[local], seg.doc_offsets[local + 1]
                t = seg.terms[a:b]
                tf = seg.tfs[a:b]
            doc_term_ids.append(np.asarray(t, np.int64))
            doc_counts.append(np.asarray(tf, np.float64).astype(np.int64))
        tc = TokenizedCorpus(doc_term_ids=doc_term_ids,
                             doc_counts=doc_counts,
                             term_hashes=self.hashes.copy(),
                             num_docs=len(live_ids))
        return tc, live_ids


# ---------------------------------------------------------------------------
# the live index
# ---------------------------------------------------------------------------


class SegmentedIndex:
    """LSM-style live index: mutable delta + sealed segment stack +
    tombstones, queried by the fused candidates engine per segment.

    See the module docstring for the lifecycle and the exact-ranking /
    recompile-avoidance contracts.  ``ranking`` (``core/ranking.py``)
    is the index's for its lifetime: tf-idf cosine unless given.
    """

    def __init__(self, term_hashes: np.ndarray | None = None, *,
                 delta_doc_capacity: int = 512,
                 delta_posting_capacity: int | None = None,
                 policy: compaction.TieredPolicy | None = None,
                 rank_seed: int = 7, seal_layout: str = "hor",
                 layout_policy: size_model.LayoutCostModel | None = None,
                 event_capacity: int = 256,
                 ranking: Ranking = TFIDF_COSINE):
        if seal_layout not in ("hor", "packed", "banded"):
            raise ValueError(f"unknown seal layout: {seal_layout!r}")
        self._ranking = ranking
        self._hashes = (np.asarray(term_hashes, np.uint32).copy()
                        if term_hashes is not None
                        else np.zeros(0, np.uint32))
        self._df = np.zeros(len(self._hashes), np.int64)
        self._rebuild_lookup()
        self._live = np.zeros(0, bool)
        self._rank = np.zeros(0, np.float32)
        self._doc_row = np.zeros(0, np.float32)   # the ranking's doc row
        self._dl = np.zeros(0, np.int64)          # tokens of every doc
        self._live_docs = 0
        self._live_tokens = 0
        self._segments: list[Segment] = []
        post_cap = (int(delta_posting_capacity)
                    if delta_posting_capacity is not None
                    else int(delta_doc_capacity) * 64)
        self._delta = _Delta(delta_doc_capacity, post_cap, 0)
        self._delta_dev: dict | None = None
        self._delta_dirty = True
        self._policy = policy or compaction.TieredPolicy()
        self._rng = np.random.default_rng(rank_seed)
        self._seal_layout = seal_layout
        self._layout_policy = layout_policy
        self._epoch = 0
        self._view: LiveView | None = None
        self.stats = LiveIndexStats()
        # bounded structured ring of maintenance events (seal/compact/
        # rewrite/ingest/delete/...), queryable from the serving tier;
        # the capacity is caller-sized (ServerConfig/MeshConfig plumb it
        # through) — event-heavy maintenance (banded rewrites emit one
        # event per band decision) must not silently evict the seal/
        # compact provenance the serving tier reads
        self.events = EventLog(capacity=int(event_capacity))

    # -- introspection ------------------------------------------------------

    @property
    def num_docs(self) -> int:
        """Allocated doc-id space (ids are never reused)."""
        return len(self._live)

    @property
    def live_doc_count(self) -> int:
        return self._live_docs

    @property
    def live_token_count(self) -> int:
        """Tokens (summed tfs) of the live documents, exact."""
        return self._live_tokens

    @property
    def ranking(self) -> Ranking:
        return self._ranking

    @property
    def num_segments(self) -> int:
        return len(self._segments)

    @property
    def num_terms(self) -> int:
        return len(self._hashes)

    @property
    def term_hashes(self) -> np.ndarray:
        return self._hashes

    def live_mask(self) -> np.ndarray:
        return self._live.copy()

    def segment_postings(self) -> list:
        return [s.n_postings for s in self._segments]

    def segments(self) -> list:
        """The sealed stack (ascending doc_base; treat as read-only)."""
        return list(self._segments)

    def layout_mix(self) -> dict:
        """Per-layout composition of the sealed stack (counts, docs,
        postings, chooser reasons, per-segment decisions) — what a
        campaign run reports as the mix the chooser converged to."""
        return _layout_mix(self._segments)

    @property
    def layout_policy(self) -> size_model.LayoutCostModel | None:
        """The POLICY rung of the seal-layout override ladder
        (``explicit seal(layout=...) arg > layout_policy > seal_layout``
        default).  ``None`` — the default — is bit-identical to the
        pre-chooser constants."""
        return self._layout_policy

    @layout_policy.setter
    def layout_policy(self, policy: size_model.LayoutCostModel | None):
        self._layout_policy = policy

    @property
    def delta_postings(self) -> int:
        return self._delta.n_postings

    @property
    def policy(self) -> compaction.TieredPolicy:
        return self._policy

    @property
    def delta_fill(self) -> float:
        """Fill fraction of the mutable delta (docs or postings,
        whichever is closer to capacity) — the maintenance thread's
        seal trigger."""
        dl = self._delta
        return max(dl.n_docs / dl.doc_cap, dl.n_postings / dl.post_cap)

    @property
    def epoch(self) -> int:
        """Monotonic counter of query-visible state changes.  The
        serving tier keys result caches on it: a cached (query, k,
        epoch) entry is valid iff the epoch still matches."""
        return self._epoch

    def _bump_epoch(self) -> None:
        self._epoch += 1

    def view(self) -> LiveView:
        """The epoch-pinned immutable view of the current state (cached
        per epoch).  Must be called serially with mutators — the serving
        tier holds its write lock for the pin, never for the query."""
        if self._view is not None and self._view.epoch == self._epoch:
            return self._view
        dl = self._delta
        n_p = dl.n_postings
        self._view = LiveView(
            epoch=self._epoch,
            segments=tuple(dataclasses.replace(s) for s in self._segments),
            delta_dev=self._delta_device(),
            delta_terms=dl.terms[:n_p].copy(),
            delta_tfs=dl.tfs[:n_p].copy(),
            delta_doc_of=dl.doc_of[:n_p].copy(),
            delta_doc_offsets=dl.doc_offsets[:dl.n_docs + 1].copy(),
            delta_doc_base=dl.doc_base, delta_n_docs=dl.n_docs,
            hashes=self._hashes, hash_sorted=self._hash_sorted,
            hash_order=self._hash_order, df=self._df.copy(),
            live=self._live.copy(), live_docs=self._live_docs,
            num_docs=self.num_docs, live_tokens=self._live_tokens,
            ranking=self._ranking)
        return self._view

    # -- vocabulary ---------------------------------------------------------

    def _rebuild_lookup(self) -> None:
        self._hash_order = np.argsort(self._hashes,
                                      kind="stable").astype(np.int64)
        self._hash_sorted = self._hashes[self._hash_order]

    def lookup_np(self, qh: np.ndarray) -> np.ndarray:
        """u32[...] hashes -> unified term ids (i64, -1 absent/empty)."""
        return _lookup_sorted(self._hash_sorted, self._hash_order, qh)

    # -- mutation: add ------------------------------------------------------

    def add_batch(self, corpus: TokenizedCorpus, *,
                  refresh_norms: bool = True) -> None:
        """Ingest a tokenized batch: unify vocabularies (vectorized
        remap), assign fresh ascending doc ids, append to the delta
        (sealing when full), update live df exactly, refresh norms, and
        let the tiered policy compact.

        ``refresh_norms=False`` defers the norm recomputation — an
        O(all live postings) pass per batch that turns a streaming
        build quadratic.  Norms depend only on the FINAL global df, so
        a streaming ingest loop may pass False for every batch and call
        ``self.refresh_norms()`` once at the end: the result is
        bit-identical to per-batch refreshing (the campaign's streaming
        parity test asserts this).  Until that call, every doc norm is
        0 and queries return no hits — deferral is a BUILD-loop tool,
        not a serving mode."""
        t0 = time.perf_counter()
        nd = corpus.num_docs
        merged, remap = build_mod.merge_vocab(
            self._hashes, np.asarray(corpus.term_hashes, np.uint32))
        if len(merged) != len(self._hashes):
            grow = len(merged) - len(self._hashes)
            self._hashes = merged
            self._df = np.concatenate(
                [self._df, np.zeros(grow, np.int64)])
            self._rebuild_lookup()
        if nd == 0:
            return
        lens = np.array([len(x) for x in corpus.doc_term_ids],
                        dtype=np.int64)
        total = int(lens.sum())
        if total:
            flat_terms = remap[
                np.concatenate(corpus.doc_term_ids).astype(np.int64)]
            flat_tfs = np.concatenate(corpus.doc_counts).astype(np.float32)
            doc_idx = np.repeat(np.arange(nd, dtype=np.int64), lens)
            # per-doc ascending UNIFIED term order: the remap can break
            # the corpus-local ordering, and norm bit-parity with the
            # term-major bulk sort depends on it
            order = np.lexsort((flat_terms, doc_idx))
            flat_terms = flat_terms[order]
            flat_tfs = flat_tfs[order]
            dl = np.rint(np.bincount(doc_idx, weights=flat_tfs,
                                     minlength=nd)).astype(np.int64)
        else:
            flat_terms = np.zeros(0, np.int64)
            flat_tfs = np.zeros(0, np.float32)
            dl = np.zeros(nd, np.int64)

        self._live = np.concatenate([self._live, np.ones(nd, bool)])
        self._rank = np.concatenate(
            [self._rank,
             (self._rng.random(nd) * 1e-3).astype(np.float32)])
        self._doc_row = np.concatenate(
            [self._doc_row, np.zeros(nd, np.float32)])
        self._dl = np.concatenate([self._dl, dl])
        if total:
            self._df += np.bincount(flat_terms,
                                    minlength=len(self._hashes))
        self._live_docs += nd
        self._live_tokens += int(dl.sum())
        self.stats.postings_appended += total
        self.stats.docs_added += nd

        doc_starts = np.zeros(nd + 1, np.int64)
        np.cumsum(lens, out=doc_starts[1:])
        d = 0
        while d < nd:
            free_docs = self._delta.doc_cap - self._delta.n_docs
            free_posts = self._delta.post_cap - self._delta.n_postings
            cum = doc_starts[d:] - doc_starts[d]
            m = int(np.searchsorted(cum, free_posts, side="right")) - 1
            m = min(m, free_docs, nd - d)
            if m <= 0:
                if self._delta.n_docs > 0:
                    self._seal_delta()
                    continue
                # a single doc larger than the delta's posting capacity:
                # seal it directly as its own segment
                s, e = doc_starts[d], doc_starts[d + 1]
                self._direct_seal(flat_terms[s:e], flat_tfs[s:e])
                d += 1
                continue
            s, e = doc_starts[d], doc_starts[d + m]
            self._delta.append(lens[d:d + m], flat_terms[s:e],
                               flat_tfs[s:e])
            d += m
        self._delta_dirty = True
        if refresh_norms:
            self._refresh_norms()
        self._maybe_compact()
        self._bump_epoch()
        self.events.emit(
            "ingest", epoch=self._epoch, docs=nd, postings=total,
            norms_refreshed=bool(refresh_norms),
            duration_us=(time.perf_counter() - t0) * 1e6)

    def refresh_norms(self) -> None:
        """Recompute every live doc norm from the current global df and
        push the refreshed metadata to each segment's device DocTable.
        Streaming builds that deferred per-batch refreshes
        (``add_batch(..., refresh_norms=False)``) MUST call this before
        serving queries."""
        t0 = time.perf_counter()
        self._refresh_norms()
        self._bump_epoch()
        self.events.emit(
            "norm_refresh", epoch=self._epoch,
            postings=self.stats.postings_norm_refreshed,
            duration_us=(time.perf_counter() - t0) * 1e6)

    def _direct_seal(self, terms: np.ndarray, tfs: np.ndarray) -> None:
        """Seal one oversized doc straight to a segment, bypassing the
        delta (which must be empty; its base advances past the doc)."""
        assert self._delta.n_docs == 0
        t0 = time.perf_counter()
        base = self._delta.doc_base
        doc_of = np.zeros(len(terms), np.int64)
        seg = self._build_segment(base, 1, doc_of, terms.astype(np.int64),
                                  tfs)
        self._segments.append(seg)
        self.stats.postings_sealed += len(terms)
        self.stats.seals += 1
        self._delta = _Delta(self._delta.doc_cap, self._delta.post_cap,
                             base + 1)
        self._delta_dirty = True
        self._bump_epoch()
        self.events.emit(
            "seal", epoch=self._epoch, doc_base=seg.doc_base,
            docs=seg.doc_span, postings=seg.n_postings,
            size_class=seg.size_class, layout=seg.layout,
            band_cut=seg.band_cut,
            chooser_reason=seg.chooser_reason, direct=True,
            duration_us=(time.perf_counter() - t0) * 1e6)

    # -- mutation: delete ---------------------------------------------------

    def delete(self, doc_ids) -> None:
        """Tombstone documents: mark dead, decrement live df using the
        forward postings, refresh norms (dead norm -> 0, which every
        engine's deleted-doc mask honours in-kernel).  Postings stay in
        place until compaction reclaims them.  Already-dead ids are
        ignored; out-of-range ids raise."""
        ids = np.atleast_1d(np.asarray(doc_ids, np.int64))
        if ids.size == 0:
            return
        if ids.min() < 0 or ids.max() >= self.num_docs:
            raise ValueError(f"doc id out of range [0, {self.num_docs})")
        ids = np.unique(ids)
        ids = ids[self._live[ids]]
        if ids.size == 0:
            return
        for d in ids:
            terms = self._doc_terms(int(d))
            if len(terms):
                self._df[terms.astype(np.int64)] -= 1
        self._live[ids] = False
        self._live_docs -= int(ids.size)
        self._live_tokens -= int(self._dl[ids].sum())
        self.stats.deletes += int(ids.size)
        self._refresh_norms()
        self._bump_epoch()
        self.events.emit("delete", epoch=self._epoch, docs=int(ids.size),
                         live_docs=self._live_docs)

    def _owner(self, d: int):
        """Segment index owning global doc id d, or None for the delta."""
        if d >= self._delta.doc_base:
            return None
        bases = [s.doc_base for s in self._segments]
        i = bisect.bisect_right(bases, d) - 1
        seg = self._segments[i]
        assert seg.doc_base <= d < seg.doc_base + seg.doc_span
        return i

    def _doc_terms(self, d: int) -> np.ndarray:
        o = self._owner(d)
        if o is None:
            dl = self._delta
            local = d - dl.doc_base
            if local >= dl.n_docs:
                return np.zeros(0, np.int32)
            s, e = dl.doc_offsets[local], dl.doc_offsets[local + 1]
            return dl.terms[s:e]
        seg = self._segments[o]
        local = d - seg.doc_base
        s, e = seg.doc_offsets[local], seg.doc_offsets[local + 1]
        return seg.terms[s:e]

    # -- seal / compact -----------------------------------------------------

    def seal(self, layout: str | None = None) -> None:
        """Flush the delta into a sealed segment (no-op when empty).

        ``layout`` overrides the index's ``seal_layout`` for this seal:
        ``"hor"`` emits 128-lane HOR blocks, ``"packed"`` emits
        delta+bit-packed blocks (same size-class quantization, same
        fused-engine entry points, parity-tested against HOR)."""
        self._seal_delta(layout=layout)

    def _seal_delta(self, layout: str | None = None) -> None:
        dl = self._delta
        if dl.n_docs == 0:
            return
        t0 = time.perf_counter()
        n_p = dl.n_postings
        doc_of = dl.doc_of[:n_p].astype(np.int64)
        terms = dl.terms[:n_p].astype(np.int64)
        tfs = dl.tfs[:n_p].copy()
        live = self._live[doc_of + dl.doc_base]
        if not live.all():
            doc_of, terms, tfs = doc_of[live], terms[live], tfs[live]
        seg = self._build_segment(dl.doc_base, dl.n_docs, doc_of, terms,
                                  tfs, layout=layout)
        self._segments.append(seg)
        self.stats.postings_sealed += n_p
        self.stats.seals += 1
        self._delta = _Delta(dl.doc_cap, dl.post_cap,
                             dl.doc_base + dl.n_docs)
        self._delta_dirty = True
        self._bump_epoch()
        self.events.emit(
            "seal", epoch=self._epoch, doc_base=seg.doc_base,
            docs=seg.doc_span, postings=seg.n_postings,
            size_class=seg.size_class, layout=seg.layout,
            band_cut=seg.band_cut,
            chooser_reason=seg.chooser_reason,
            duration_us=(time.perf_counter() - t0) * 1e6)

    def _build_segment(self, base: int, span: int, doc_of: np.ndarray,
                       terms: np.ndarray, tfs: np.ndarray,
                       layout: str | None = None,
                       band_cut: int | None = None) -> Segment:
        """Bulk-build one sealed segment over LOCAL doc ids and pad it to
        its size class.  ``doc_of``/``terms``/``tfs`` must be (doc,
        term)-sorted.

        ``layout`` resolution is the override ladder: an explicit arg
        wins, else the installed ``layout_policy`` chooses from this
        run's measured shape, else the constructor's ``seal_layout``
        default — so seal AND compaction both funnel through the
        chooser, which is what makes merged (hot) segments converge to
        the winning layout over the LSM lifecycle."""
        w = len(self._hashes)
        d_pad = layouts.size_class(span, base=layouts.ROUTE_TILE)
        order = np.lexsort((doc_of, terms))          # term-major for bulk
        df_seg = (np.bincount(terms, minlength=w) if len(terms)
                  else np.zeros(w, np.int64))
        n_terms_seg = int(np.count_nonzero(df_seg))
        run_stats = size_model.SegmentStats(
            num_docs=int(span), num_postings=len(terms),
            num_terms=n_terms_seg)
        layout, reason = size_model.resolve_layout(
            layout, self._layout_policy, run_stats, self._seal_layout,
            size_class=d_pad)
        if layout not in ("hor", "packed", "banded"):
            raise ValueError(f"unknown seal layout: {layout!r}")
        # seal/compaction emit segments already tuned for their size
        # class: the routing cache is built at the tile width the active
        # tuning table picked for (pallas, d_pad, layout) — queries at
        # other widths fall back to the scaled budget path
        route_tile = autotune.lookup("pallas", d_pad, layout).tile
        offsets = np.zeros(w + 1, np.int64)
        np.cumsum(df_seg, out=offsets[1:])
        norm_pad = np.zeros(d_pad, np.float32)
        rank_pad = np.zeros(d_pad, np.float32)
        norm_pad[:span] = self._doc_row[base:base + span]
        rank_pad[:span] = self._rank[base:base + span]
        host = PostingsHost(
            term_hashes=self._hashes, df=df_seg.astype(np.int32),
            offsets=offsets, doc_ids=doc_of[order].astype(np.int32),
            tfs=tfs[order].astype(np.float32), num_docs=d_pad,
            norm=norm_pad, rank=rank_pad)
        cut = 0
        if layout == "banded":
            # band cut: explicit (snapshot restore reproduces the build
            # bitwise) or byte-model-chosen; lane_quantum=8 prices the
            # cut at the packed lane-dim padding applied just below
            bix = layouts.build_banded(host, max_band_words=band_cut,
                                       route_tile=route_tile,
                                       lane_quantum=8)
            # record the REALIZED pre-pad packed stride as the cut: no
            # term has a width in (realized max, chooser threshold], so
            # rebuilding with it reproduces the same band split — the
            # post-pad stride (multiple of 8) would NOT (it could admit
            # wider terms on restore)
            cut = int(bix.packed.words_per_block)
            p = bix.packed
            p = layouts.pad_packed_to_class(
                p,
                nb_pad=layouts.size_class(int(p.packed.shape[0])),
                w_pad=layouts.size_class(w, base=256),
                max_posting_len=layouts.size_class(p.max_posting_len),
                words_per_block=-(-p.words_per_block // 8) * 8,
                route_pairs_max=layouts.size_class(p.route_pairs_max),
                route_span_max=layouts.size_class(p.route_span_max,
                                                  base=8))
            hx = bix.hor
            mpl_q = layouts.size_class(hx.max_posting_len)
            hx = layouts.pad_blocked_to_class(
                hx,
                nb_pad=layouts.size_class(int(hx.block_docs.shape[0])),
                w_pad=layouts.size_class(w, base=256),
                max_posting_len=mpl_q,
                max_blocks_per_term=mpl_q // layouts.BLOCK,
                route_pairs_max=layouts.size_class(hx.route_pairs_max),
                route_span_max=layouts.size_class(hx.route_span_max,
                                                  base=8))
            # padding rebuilt per-band arrays; re-share the DocTable and
            # the (identical-content) vocabulary buffer across bands
            hx = dataclasses.replace(hx, docs=p.docs,
                                     sorted_hash=p.sorted_hash)
            ix = layouts.BandedCsrIndex(packed=p, hor=hx)
        elif layout == "packed":
            ix = layouts.build_packed_csr(host, route_tile=route_tile)
            ix = layouts.pad_packed_to_class(
                ix,
                nb_pad=layouts.size_class(int(ix.packed.shape[0])),
                w_pad=layouts.size_class(w, base=256),
                max_posting_len=layouts.size_class(ix.max_posting_len),
                # the packed id plane is THE roofline term packed wins
                # on, so its lane dim pads arithmetically (next multiple
                # of 8 words) instead of geometrically: doubling 52 ->
                # 64 words would stream back ~6% of the per-block win
                # as padding on every routed block
                words_per_block=-(-ix.words_per_block // 8) * 8,
                route_pairs_max=layouts.size_class(ix.route_pairs_max),
                route_span_max=layouts.size_class(ix.route_span_max,
                                                  base=8))
        else:
            ix = layouts.build_blocked(host, route_tile=route_tile)
            nb = int(ix.block_docs.shape[0])
            mpl_q = layouts.size_class(ix.max_posting_len)
            ix = layouts.pad_blocked_to_class(
                ix,
                nb_pad=layouts.size_class(nb),
                w_pad=layouts.size_class(w, base=256),
                max_posting_len=mpl_q,
                max_blocks_per_term=mpl_q // layouts.BLOCK,
                route_pairs_max=layouts.size_class(ix.route_pairs_max),
                route_span_max=layouts.size_class(ix.route_span_max,
                                                  base=8))
        if self._ranking.saturates:
            # the builders lay a cosine DocTable out; this ranking's row
            # is the length row, and no norm row is kept
            ix = _with_docs(ix, self._ranking.doc_table(norm_pad, rank_pad))
        doc_offsets = np.zeros(span + 1, np.int64)
        np.cumsum(np.bincount(doc_of.astype(np.int64), minlength=span),
                  out=doc_offsets[1:])
        return Segment(index=ix, doc_base=int(base), doc_span=int(span),
                       doc_of=doc_of.astype(np.int32),
                       terms=terms.astype(np.int32),
                       tfs=tfs.astype(np.float32),
                       doc_offsets=doc_offsets, n_postings=len(terms),
                       size_class=int(d_pad), num_terms=n_terms_seg,
                       chooser_reason=reason, band_cut=cut)

    def compact(self, all_segments: bool = False) -> bool:
        """Merge a policy-picked run of adjacent segments into one,
        physically dropping tombstoned postings (their ids stay dead —
        never reused).  ``all_segments=True`` rewrites the whole stack
        into a single segment (the compat wrapper's full merge).
        Returns True if a merge happened."""
        n = len(self._segments)
        if all_segments:
            pick = (0, n) if n >= 1 else None
        else:
            pick = self._policy.pick(
                [s.n_postings for s in self._segments])
        if pick is None:
            return False
        t0 = time.perf_counter()
        lo, hi = pick
        segs = self._segments[lo:hi]
        base = segs[0].doc_base
        span = segs[-1].doc_base + segs[-1].doc_span - base
        parts_d, parts_t, parts_f = [], [], []
        touched = 0
        for s in segs:
            touched += s.n_postings
            if s.n_postings == 0:
                continue
            live = self._live[s.doc_of.astype(np.int64) + s.doc_base]
            parts_d.append(s.doc_of[live].astype(np.int64) +
                           (s.doc_base - base))
            parts_t.append(s.terms[live].astype(np.int64))
            parts_f.append(s.tfs[live])
        if parts_d:
            doc_of = np.concatenate(parts_d)
            terms = np.concatenate(parts_t)
            tfs = np.concatenate(parts_f)
            order = np.lexsort((terms, doc_of))      # doc-major canonical
            doc_of, terms, tfs = doc_of[order], terms[order], tfs[order]
        else:
            doc_of = np.zeros(0, np.int64)
            terms = np.zeros(0, np.int64)
            tfs = np.zeros(0, np.float32)
        seg = self._build_segment(base, span, doc_of, terms, tfs)
        self._segments[lo:hi] = [seg]
        self.stats.postings_compacted += touched
        self.stats.compactions += 1
        self._bump_epoch()
        self.events.emit(
            "compact", epoch=self._epoch, merged=hi - lo,
            doc_base=seg.doc_base, docs=seg.doc_span,
            postings_in=touched, postings_out=seg.n_postings,
            size_class=seg.size_class, layout=seg.layout,
            band_cut=seg.band_cut,
            chooser_reason=seg.chooser_reason,
            duration_us=(time.perf_counter() - t0) * 1e6)
        return True

    def _maybe_compact(self) -> None:
        while self.compact():
            pass

    def pick_layout_rewrite(self) -> int | None:
        """Position of the oldest sealed segment whose layout disagrees
        with the installed ``layout_policy`` (None when no policy, or
        the stack already converged).  O(num_segments) on stored run
        stats — no posting data touched.  The decision re-evaluates the
        SAME stats ``rewrite_segment`` will rebuild with, so a rewrite
        can never oscillate."""
        if self._layout_policy is None:
            return None
        current = [s.layout for s in self._segments]
        wanted = [self._layout_policy.choose(
            s.stats, size_class=s.size_class).layout
            for s in self._segments]
        return compaction.pick_layout_rewrite(current, wanted)

    def rewrite_segment(self, i: int) -> None:
        """Re-seal segment ``i`` in place through the layout ladder
        (policy decides — there is no explicit arg here), physically
        dropping its tombstoned postings.  Doc ids, norms, and scores
        are unchanged: the rebuilt segment answers bit-identically in
        either layout (the layout-parity contract).  Epoch advances so
        serving tiers repin."""
        seg = self._segments[i]
        t0 = time.perf_counter()
        live = self._live[seg.doc_of.astype(np.int64) + seg.doc_base]
        doc_of = seg.doc_of[live].astype(np.int64)
        terms = seg.terms[live].astype(np.int64)
        tfs = seg.tfs[live]
        new = self._build_segment(seg.doc_base, seg.doc_span, doc_of,
                                  terms, tfs)
        self._segments[i] = new
        self.stats.postings_compacted += seg.n_postings
        self.stats.layout_rewrites += 1
        self._bump_epoch()
        self.events.emit(
            "rewrite", epoch=self._epoch, position=i,
            doc_base=new.doc_base, docs=new.doc_span,
            from_layout=seg.layout, layout=new.layout,
            postings_in=seg.n_postings, postings_out=new.n_postings,
            size_class=new.size_class, band_cut=new.band_cut,
            chooser_reason=new.chooser_reason,
            duration_us=(time.perf_counter() - t0) * 1e6)

    # -- norms / doc metadata ----------------------------------------------

    def _refresh_norms(self) -> None:
        """Recompute every doc's ranking row (``Ranking.doc_rows``) with
        the CURRENT live df, doc count and token count, and push the
        rows to each segment's device DocTable.  Under cosine that is
        every live doc's tf-idf norm, from the same float64 bincount
        (per-doc ascending-term accumulation order) as the bulk builder,
        so norms are bit-identical to a rebuild; live empty docs get
        1e-12.  Dead docs get row 0 (the tombstone mask every engine
        honours)."""
        dl = self._delta
        n_p = dl.n_postings

        def postings():
            # one source at a time: a global id column per segment
            for seg in self._segments:
                if seg.n_postings:
                    yield (seg.doc_of.astype(np.int64) + seg.doc_base,
                           seg.terms, seg.tfs)
            if n_p:
                yield (dl.doc_of[:n_p].astype(np.int64) + dl.doc_base,
                       dl.terms[:n_p], dl.tfs[:n_p])

        self._doc_row = self._ranking.doc_rows(
            live=self._live, df=self._df, live_docs=self._live_docs,
            dl=self._dl, live_tokens=self._live_tokens, postings=postings())
        if not self._ranking.saturates:
            self.stats.postings_norm_refreshed += n_p + sum(
                s.n_postings for s in self._segments)
        for seg in self._segments:
            self._push_doc_meta(seg)
        self._delta_dirty = True

    def _push_doc_meta(self, seg: Segment) -> None:
        d_pad = seg.index.docs.num_docs
        row_pad = np.zeros(d_pad, np.float32)
        row_pad[:seg.doc_span] = self._doc_row[
            seg.doc_base:seg.doc_base + seg.doc_span]
        seg.index = _with_docs(seg.index, self._ranking.doc_table(
            row_pad, seg.index.docs.rank))

    def _delta_device(self) -> dict:
        if self._delta_dev is None or self._delta_dirty:
            dl = self._delta
            row = np.zeros(dl.doc_cap, np.float32)
            rank = np.zeros(dl.doc_cap, np.float32)
            hi = min(dl.doc_base + dl.doc_cap, self.num_docs)
            n = max(hi - dl.doc_base, 0)
            row[:n] = self._doc_row[dl.doc_base:hi]
            rank[:n] = self._rank[dl.doc_base:hi]
            self._delta_dev = {
                "terms": jnp.asarray(dl.terms),
                "tfs": jnp.asarray(dl.tfs),
                "doc_of": jnp.asarray(dl.doc_of),
                "row": jnp.asarray(row),
                "rank": jnp.asarray(rank),
            }
            self._delta_dirty = False
        return self._delta_dev

    # -- queries ------------------------------------------------------------

    def topk(self, query_hashes, k: int, *, cap: int | None = None,
             rank_blend: float = 0.0, engine: str = "pallas",
             mode: str = "candidates", backend: str = "pallas",
             return_stats: bool = False, tune=None, trace=None):
        """Batched top-k over delta + every sealed segment.

        query_hashes u32[B, T].  One fused candidate-kernel launch per
        sealed segment (``engine="pallas"``, the default; ``mode=
        "dense"`` keeps the PR-1 dense tail, ``engine="jnp"`` is the
        gather oracle) + one static-shape delta evaluation; per-segment
        candidate lists merge on the host with the oracle's tie order.
        ``cap`` defaults to each segment's (quantized) full posting
        length — the exact-parity setting.  Evaluates against the
        current epoch's pinned view (``view()``), which is also what the
        serving tier queries directly.  ``tune`` overrides the active
        tuning table's per-segment kernel geometry (see
        ``LiveView.topk``)."""
        return self.view().topk(query_hashes, k, cap=cap,
                                rank_blend=rank_blend, engine=engine,
                                mode=mode, backend=backend,
                                return_stats=return_stats, tune=tune,
                                trace=trace)

    def conjunctive(self, query_hashes, k: int, cap: int):
        """AND semantics over the whole live index for ONE query [T].

        Each sealed segment contributes its local membership counts
        (docs live in exactly one segment, so local == global) and its
        own cap-truncation count; ``stats["truncated_terms"]``
        AGGREGATES across segments — truncation in ANY segment is
        surfaced, not just the last one scored."""
        return self.view().conjunctive(query_hashes, k, cap)

    # -- import / export ----------------------------------------------------

    @classmethod
    def from_host(cls, host: PostingsHost, **kwargs) -> "SegmentedIndex":
        """Seed a live index from bulk-built postings: one sealed
        segment over [0, num_docs), the host's vocabulary and static
        ranks, norms recomputed (identically) from live df."""
        si = cls(term_hashes=host.term_hashes, **kwargs)
        if host.num_docs == 0:
            return si
        si._live = np.ones(host.num_docs, bool)
        si._rank = host.rank.astype(np.float32).copy()
        si._doc_row = np.zeros(host.num_docs, np.float32)
        si._df = host.df.astype(np.int64).copy()
        si._live_docs = host.num_docs
        term_of = np.repeat(np.arange(host.num_terms, dtype=np.int64),
                            np.diff(host.offsets))
        doc = host.doc_ids.astype(np.int64)
        si._dl = np.rint(np.bincount(doc, weights=host.tfs,
                                     minlength=host.num_docs)
                         ).astype(np.int64)
        si._live_tokens = int(si._dl.sum())
        order = np.lexsort((term_of, doc))           # doc-major canonical
        seg = si._build_segment(0, host.num_docs, doc[order],
                                term_of[order],
                                host.tfs[order].astype(np.float32))
        si._segments.append(seg)
        si.stats.postings_sealed += seg.n_postings
        si.stats.seals += 1
        si._delta = _Delta(si._delta.doc_cap, si._delta.post_cap,
                           host.num_docs)
        si._refresh_norms()
        si._bump_epoch()
        si.events.emit(
            "seal", epoch=si._epoch, doc_base=0, docs=seg.doc_span,
            postings=seg.n_postings, size_class=seg.size_class,
            layout=seg.layout, band_cut=seg.band_cut,
            chooser_reason=seg.chooser_reason,
            via="from_host")
        return si

    def _live_triples(self):
        parts_d, parts_t, parts_f = [], [], []
        for seg in self._segments:
            if seg.n_postings == 0:
                continue
            gdoc = seg.doc_of.astype(np.int64) + seg.doc_base
            live = self._live[gdoc]
            parts_d.append(gdoc[live])
            parts_t.append(seg.terms[live].astype(np.int64))
            parts_f.append(seg.tfs[live])
        dl = self._delta
        if dl.n_postings:
            gdoc = dl.doc_of[:dl.n_postings].astype(np.int64) + dl.doc_base
            live = self._live[gdoc]
            parts_d.append(gdoc[live])
            parts_t.append(dl.terms[:dl.n_postings][live].astype(np.int64))
            parts_f.append(dl.tfs[:dl.n_postings][live])
        if not parts_d:
            return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                    np.zeros(0, np.float32))
        return (np.concatenate(parts_d), np.concatenate(parts_t),
                np.concatenate(parts_f))

    def to_host(self) -> PostingsHost:
        """Export merged live postings as §3.6 bulk output (the compat
        wrapper's return).  Doc ids keep their global values; with
        tombstones present the dead ids export as deleted (norm 0) empty
        docs, and the export's norms use the allocated id count as D —
        build the oracle from ``export_live_corpus`` when an exact
        live-corpus reference is needed."""
        gdoc, terms, tfs = self._live_triples()
        host = build_mod._postings_from_triples(
            gdoc, terms, tfs.astype(np.float64), len(self._hashes),
            self.num_docs, self._hashes)
        if not self._live.all():
            norm = host.norm.copy()
            norm[~self._live] = 0.0
            host = dataclasses.replace(host, norm=norm)
        return host

    def export_live_corpus(self):
        """The equivalent live corpus over the unified vocabulary, plus
        the ascending global ids of its docs — exactly what the parity
        oracle should ``bulk_build`` (compact renumbering preserves doc
        order, so tie-breaking maps 1:1)."""
        return self.view().export_live_corpus()
