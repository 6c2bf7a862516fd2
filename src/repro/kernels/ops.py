"""jit'd public wrappers around the Pallas kernels (+ XLA fallbacks).

``backend`` selects: "pallas" (auto: compiled on TPU, interpret-mode
elsewhere — keyed on ``jax.default_backend()``), "pallas-tpu" (force
compiled), or "xla" (the ref.py oracle path — also what the multi-pod
dry-run lowers, so GSPMD sees plain HLO).

This module is also the engine layer for query evaluation: the fused
batched decode-and-score path routes a whole query batch through ONE
Pallas kernel launch — packed posting blocks are decoded in VMEM and
scored against a ``[Q, tile]`` accumulator, so the compressed bytes are
the only posting bytes that cross HBM.  ``fused_batched_scores`` is the
dense engine (full [B, num_docs] score array out);
``fused_batched_topk`` is the candidate engine (per-tile partial top-k
reduced IN VMEM — only O(B * n_tiles * k_tile) candidates reach HBM).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.layouts import (BandedCsrIndex, BlockedIndex,
                                PackedCsrIndex, unpair_tfs)
from repro.core.query import final_scores
from repro.core.ranking import TFIDF_COSINE, Ranking
from repro.kernels import ref
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_decode_score import (
    Q_PAD, build_batched_pairs, default_k_tile, extract_tile_candidates,
    fused_score_blocked_pallas, fused_score_packed_pallas,
    fused_topk_blocked_pallas, fused_topk_packed_pallas)
from repro.kernels.packed_postings import unpack_blocks_pallas
from repro.kernels.posting_score import TILE, build_pairs, posting_score_pallas
from repro.kernels.segment_multi_agg import pna_multi_agg_pallas

Array = jax.Array
Backend = Literal["pallas", "pallas-tpu", "xla"]


def _interp(backend: Backend) -> bool | None:
    """None -> auto (compiled iff jax.default_backend() == "tpu")."""
    return None if backend == "pallas" else False


def _count_capacity_pressure(name: str, amount) -> None:
    """Host-side increment of a process-global registry counter —
    invoked from inside jitted code via ``jax.debug.callback``, so the
    engines stay pure jax while the pressure is still countable."""
    from repro.obs.registry import GLOBAL
    GLOBAL.counter(name).inc(int(amount))


def warn_on_overflow(overflow, label: str) -> None:
    """Routing overflow is surfaced, never silent — shared by every
    engine entry point so the contract can't drift between them.  Each
    overflow also increments the process-global ``engine_pair_overflow``
    registry counter (same taken-branch — zero work when clean).

    A host int (the live view has read its counts already) is checked
    on the host, with no device work and nothing to compile; a traced
    array (inside ``jit``) takes a ``lax.cond`` whose taken branch
    prints and counts through host callbacks."""
    message = (label + ": routing overflow dropped {o} (block, tile) "
               "pairs — raise max_pairs")
    if isinstance(overflow, (int, float)):
        if overflow > 0:
            print(message.format(o=overflow))
            _count_capacity_pressure("engine_pair_overflow", overflow)
        return

    def _warn(o):
        jax.debug.print(message, o=o)
        jax.debug.callback(
            functools.partial(_count_capacity_pressure,
                              "engine_pair_overflow"), o)

    jax.lax.cond(overflow > 0, _warn, lambda o: None, overflow)


def record_truncated(truncated, counter: str = "engine_truncated_terms"
                     ) -> None:
    """Count conjunctive cap-truncation into the process-global
    registry.  Accepts a host int (counted directly) or a traced array
    (counted via ``jax.debug.callback`` on the taken branch) — callers
    keep returning the stat either way; this only makes the pressure
    visible per process instead of per call site."""
    if isinstance(truncated, (int, float)):
        if truncated > 0:
            _count_capacity_pressure(counter, truncated)
        return
    jax.lax.cond(
        truncated > 0,
        lambda t: jax.debug.callback(
            functools.partial(_count_capacity_pressure, counter), t),
        lambda t: None, truncated)


# ---------------------------------------------------------------------------
# posting-list scoring over a BlockedIndex (the paper's q_occ hot path)
# ---------------------------------------------------------------------------


def routing_spans(index: BlockedIndex | PackedCsrIndex, tile: int):
    """(tile_first, tile_count, n_tiles) for ``tile``-wide doc tiles.

    Uses the index's build-time pair-routing cache when ``tile`` matches
    its ``route_tile``; otherwise derives spans from the per-block
    min/max summaries (cheap, but per-trace instead of per-build).
    """
    num_docs = index.docs.num_docs
    n_tiles = max(-(-num_docs // tile), 1)
    if tile == index.route_tile and index.tile_first is not None:
        return index.tile_first, index.tile_count, n_tiles
    has = index.block_max >= 0
    t0 = jnp.clip(index.block_min // tile, 0, n_tiles - 1)
    t1 = jnp.clip(index.block_max // tile, 0, n_tiles - 1)
    return (jnp.where(has, t0, 0).astype(jnp.int32),
            jnp.where(has, t1 - t0 + 1, 0).astype(jnp.int32), n_tiles)


def select_query_blocks(index: BlockedIndex, term_ids: Array, idf_w: Array,
                        max_blocks_per_term: int):
    """Selected (global block id, validity, per-block weight) for a query."""
    safe = jnp.maximum(term_ids, 0)
    start = index.block_offsets[safe]
    nb = index.block_offsets[safe + 1] - start
    k = jnp.arange(max_blocks_per_term, dtype=jnp.int32)
    sel = (start[:, None] + k[None, :])
    valid = (k[None, :] < nb[:, None]) & (term_ids >= 0)[:, None]
    sel = jnp.where(valid, sel, 0)
    w = jnp.broadcast_to(idf_w[:, None], sel.shape)
    return sel.reshape(-1), valid.reshape(-1), w.reshape(-1)


def blocked_query_scores(index: BlockedIndex, term_ids: Array, idf_w: Array,
                         max_blocks_per_term: int, max_pairs: int,
                         tile: int = TILE,
                         backend: Backend = "pallas") -> Array:
    """Dense per-doc scores for ONE query via the posting_score kernel."""
    sel, valid, w = select_query_blocks(index, term_ids, idf_w,
                                        max_blocks_per_term)
    num_docs = index.docs.num_docs
    if backend == "xla":
        bd = jnp.where(valid[:, None], index.block_docs[sel], -1)
        bt = jnp.where(valid[:, None], index.block_tfs[sel], 0.0)
        return ref.ref_posting_score(bd, bt, w * valid, num_docs)
    tfirst, tcount, n_tiles = routing_spans(index, tile)
    pb, pt, pw, _overflow = build_pairs(sel, valid, w, tfirst, tcount,
                                        n_tiles, max_pairs)
    return posting_score_pallas(index.block_docs, index.block_tfs,
                                pb, pt, pw, num_docs, tile,
                                interpret=_interp(backend))


# ---------------------------------------------------------------------------
# fused batched decode-and-score (the engine hot path)
# ---------------------------------------------------------------------------


def term_pairs_bound(n_terms: int, m_blocks: int, n_tiles: int) -> int:
    """Most routing pairs ``n_terms`` posting lists of at most
    ``m_blocks`` blocks each can produce over ``n_tiles`` doc tiles.

    A term's blocks are doc-ordered and disjoint, so consecutive blocks
    share at most their boundary tile: one term's (block, tile) pairs
    number at most ``n_tiles + m_blocks - 1``, at any tile width."""
    return int(n_terms) * (int(n_tiles) + max(int(m_blocks), 1) - 1)


def default_max_pairs(index: BlockedIndex | PackedCsrIndex, num_queries: int,
                      num_terms: int, cap: int, tile: int = TILE) -> int:
    """Static routing-pair budget for a batch.

    After cross-query dedup, pairs are unique (block, tile) — bounded by
    the whole index's span sum (``route_pairs_max``), by candidate-count
    x worst single-block span, and by ``term_pairs_bound`` over the
    batch's term slots.  All three bounds are exact upper bounds, so
    overflow is impossible at the default tile; for other widths the
    span scales by ``route_tile / tile``.  At a million-doc class this is
    a few hundred thousand pairs for a served 8x8 batch where the
    whole-index ``scaled_pairs_budget`` is 2**27: the difference between
    a batch whose pair weights fit on the device and one that does not.
    """
    m = max(-(-min(cap, max(index.max_posting_len, 1)) // index.block), 1)
    if isinstance(index, BlockedIndex):
        m = min(m, max(index.max_blocks_per_term, 1))
    cands = num_queries * num_terms * m
    span = index.route_span_max
    pairs_max = index.route_pairs_max
    if tile != index.route_tile:
        scale = max(-(-index.route_tile // tile), 1)
        nb = (index.packed.shape[0] if isinstance(index, PackedCsrIndex)
              else index.block_docs.shape[0])
        span = span * scale + 1
        pairs_max = pairs_max * scale + nb
    n_tiles = max(-(-index.docs.num_docs // tile), 1)
    return max(min(pairs_max, cands * max(span, 1),
                   term_pairs_bound(num_queries * num_terms, m, n_tiles)), 8)


def scaled_pairs_budget(index: BlockedIndex | PackedCsrIndex,
                        tile: int = TILE) -> int:
    """Whole-index routing-pair bound at an arbitrary tile width.

    ``route_pairs_max`` is exact for ``tile == route_tile``; narrower
    tiles split each block's span into at most ``ceil(route_tile/tile)``
    extra tiles, wider tiles can only merge spans (the +NB term covers
    off-by-one tile straddles in both directions).  This is what the
    segment engines pass as their static ``max_pairs`` when an autotuned
    config retunes ``tile`` away from the seal-time route tile.
    """
    if tile == index.route_tile:
        return int(index.route_pairs_max)
    scale = max(-(-index.route_tile // tile), 1)
    nb = (index.packed.shape[0] if isinstance(index, PackedCsrIndex)
          else index.block_docs.shape[0])
    return max(int(index.route_pairs_max) * scale + int(nb), 8)


def expand_block_candidates(block_offsets: Array, term_ids: Array,
                            idf_w: Array, m: int, block: int,
                            cap: int | None = None):
    """Flat candidate (query, term, block) triples for a term batch.

    term_ids i32[B, T] (-1 absent), idf_w f32[B, T].  Shared by the
    single-node fused engine and the doc-sharded shard_map scorer so cap
    handling stays in lockstep.  Returns
    (cand_block, cand_valid, cand_q, cand_w, cand_cap) flattened to
    [B*T*m]; cand_cap is None when ``cap`` is None (read whole blocks).
    """
    b, t = term_ids.shape
    safe = jnp.maximum(term_ids, 0)
    start = block_offsets[safe]
    nb = block_offsets[safe + 1] - start
    k = jnp.arange(m, dtype=jnp.int32)
    cand_block = (start[..., None] + k).reshape(-1)
    cand_valid = ((k < jnp.minimum(nb, m)[..., None]) &
                  (term_ids >= 0)[..., None]).reshape(-1)
    cand_q = jnp.broadcast_to(
        jnp.arange(b, dtype=jnp.int32)[:, None, None], (b, t, m)).reshape(-1)
    cand_w = jnp.broadcast_to(idf_w[..., None], (b, t, m)).reshape(-1)
    cand_cap = None
    if cap is not None:
        # lanes of the k-th block the posting cap still permits — a cap
        # cutting mid-block truncates the last block, like the oracle
        cand_cap = jnp.broadcast_to(
            jnp.clip(cap - k * block, 0, block)[None, None, :],
            (b, t, m)).reshape(-1)
    return cand_block, cand_valid, cand_q, cand_w, cand_cap


def fused_batched_scores(index: BlockedIndex | PackedCsrIndex,
                         term_ids: Array, idf_w: Array, cap: int,
                         max_pairs: int | None = None, tile: int = TILE,
                         backend: Backend = "pallas", q_pad: int = Q_PAD):
    """Dense scores f32[B, num_docs] for a BATCH of queries in one fused
    kernel launch, plus the routing-overflow counter.

    term_ids i32[B, T] (-1 absent), idf_w f32[B, T] per-slot weights.
    ``cap`` bounds postings read per term at POSTING granularity (the
    last selected block is lane-masked), matching the jnp oracle's
    gather cap exactly.
    """
    b, t = term_ids.shape
    block = index.block
    num_docs = index.docs.num_docs
    m = max(-(-min(cap, max(index.max_posting_len, 1)) // block), 1)
    if isinstance(index, BlockedIndex):
        m = min(m, max(index.max_blocks_per_term, 1))
    if max_pairs is None:
        max_pairs = default_max_pairs(index, b, t, cap, tile)

    cand_block, cand_valid, cand_q, cand_w, cand_cap = \
        expand_block_candidates(index.block_offsets, term_ids, idf_w,
                                m, block, cap)

    if backend == "xla":
        # same cross-query block dedup, lowered as plain HLO: each unique
        # block is read once and scatter-adds a [B]-wide row per posting
        # (ONE scatter for the whole batch, not one per query)
        nb_total = (index.packed.shape[0]
                    if isinstance(index, PackedCsrIndex)
                    else index.block_docs.shape[0])
        # block-level dedup only: one pair per unique block, so the
        # candidate count itself is an exact pair bound
        max_pairs = min(max_pairs, cand_block.shape[0])
        with jax.named_scope("route"):
            pb, _, pqw, pcap, overflow = build_batched_pairs(
                cand_block, cand_valid, cand_q, cand_w.astype(jnp.float32),
                jnp.zeros((nb_total,), jnp.int32),
                jnp.ones((nb_total,), jnp.int32), 1, b,
                max_pairs=max_pairs, cand_cap=cand_cap)
        if isinstance(index, PackedCsrIndex):
            docs = ref.ref_unpack_blocks(
                index.packed[pb], index.block_bits[pb],
                index.block_base[pb], index.block_count[pb], block)
            tfs = unpair_tfs(index.tf_pairs, pb)
        else:
            docs = index.block_docs[pb]
            tfs = index.block_tfs[pb]
        lane_ok = (docs >= 0) & (jnp.arange(block, dtype=jnp.int32)[None, :]
                                 < pcap[:, None])
        flat_doc = jnp.where(lane_ok, docs, num_docs).reshape(-1)
        rows = (jnp.where(lane_ok, tfs, 0.0)[:, :, None] *
                pqw[:, None, :]).reshape(-1, pqw.shape[1])
        acc = jnp.zeros((num_docs + 1, pqw.shape[1]), jnp.float32)
        acc = acc.at[flat_doc].add(rows, mode="drop")
        return acc[:num_docs].T[:b], overflow

    tfirst, tcount, n_tiles = routing_spans(index, tile)
    with jax.named_scope("route"):
        pb, pt, pqw, pcap, overflow = build_batched_pairs(
            cand_block, cand_valid, cand_q,
            cand_w.astype(jnp.float32), tfirst, tcount, n_tiles, b,
            max_pairs, cand_cap=cand_cap)

    # pad the query batch to the accumulator quantum
    bp = -(-b // max(q_pad, 1)) * max(q_pad, 1)
    if bp != b:
        pqw = jnp.pad(pqw, ((0, 0), (0, bp - b)))

    if isinstance(index, PackedCsrIndex):
        scores = fused_score_packed_pallas(
            index.packed, index.tf_pairs, pb, pt, pqw, pcap,
            index.block_bits[pb], index.block_base[pb],
            index.block_count[pb], num_docs, block, tile,
            interpret=_interp(backend))
    else:
        scores = fused_score_blocked_pallas(
            index.block_docs, index.block_tfs, pb, pt, pqw, pcap,
            num_docs, tile, interpret=_interp(backend))
    return scores[:b], overflow


def fused_batched_topk(index: BlockedIndex | PackedCsrIndex,
                       term_ids: Array, idf_w: Array, cap: int, k: int,
                       rank_blend: float = 0.0,
                       max_pairs: int | None = None, tile: int = TILE,
                       k_tile: int | None = None,
                       backend: Backend = "pallas", q_pad: int = Q_PAD,
                       reducer: str = "successive",
                       qscale: Array | None = None,
                       ranking: Ranking = TFIDF_COSINE):
    """The candidate path: per-tile partial top-k INSIDE the fused
    engine, so the dense [B, num_docs] score array never reaches HBM.

    Same contract as ``fused_batched_scores`` up to the accumulator;
    each doc tile is then reduced (in VMEM, on its last grid step) to
    ``k_tile`` (value, global doc id) candidates of FINAL score — the
    doc-metadata tail (``ranking.tail``: the deleted-doc mask, cosine's
    norm division, the rank blend) is applied per-tile, not densely.  ``k_tile`` defaults to the exactness floor
    ``min(k, tile)`` (rounded up to the lane quantum), which guarantees
    a pure ``merge_topk_candidates`` over the returned tile-major lists
    reproduces the dense oracle's top-k bit-identically.

    Returns (cand_values f32[B, n_tiles*k_tile],
    cand_ids i32[B, n_tiles*k_tile], overflow).

    ``reducer`` / ``q_pad`` are autotuner-selected
    kernel geometry (see ``kernels/autotune.py``); the defaults are the
    historical hardcoded values, so untuned callers are bit-identical
    to the pre-autotuner engine.  ``qscale`` f32[B] overrides the
    per-query scales (the live index passes the ones its ranking
    computed on the host); without it the cosine query norms of
    ``idf_w`` are taken.  ``ranking`` (``core/ranking.py``) picks the
    kernel's posting transform and tail; only the Pallas walk scores a
    saturating one.
    """
    b, t = term_ids.shape
    num_docs = index.docs.num_docs
    if k_tile is None:
        k_tile = default_k_tile(k, tile)
    k_tile = min(k_tile, tile)
    if qscale is None:
        # per-query norm of the idf weight vector (duplicate slots carry
        # 0 after dedup) — same reduction the oracle's scoring tail does
        qscale = jnp.sqrt(jnp.maximum(jnp.sum(idf_w * idf_w, axis=1),
                                      1e-12))
    row = ranking.doc_row(index.docs)

    if backend == "xla":
        if ranking.saturates:
            raise NotImplementedError(
                f"{ranking.name} is scored by the Pallas walk only; the "
                "xla lowering places weighted tfs linearly")
        # plain-HLO lowering: dense scores (same block dedup), then the
        # jnp mirror of the kernels' per-tile reduction
        scores, overflow = fused_batched_scores(
            index, term_ids, idf_w, cap, max_pairs=max_pairs, tile=tile,
            backend="xla")
        final = final_scores(scores, row, index.docs.rank, qscale,
                             rank_blend, ranking)
        vals, ids = extract_tile_candidates(final, tile, k_tile)
        return vals, ids, overflow

    block = index.block
    m = max(-(-min(cap, max(index.max_posting_len, 1)) // block), 1)
    if isinstance(index, BlockedIndex):
        m = min(m, max(index.max_blocks_per_term, 1))
    if max_pairs is None:
        max_pairs = default_max_pairs(index, b, t, cap, tile)

    cand_block, cand_valid, cand_q, cand_w, cand_cap = \
        expand_block_candidates(index.block_offsets, term_ids, idf_w,
                                m, block, cap)
    tfirst, tcount, n_tiles = routing_spans(index, tile)
    with jax.named_scope("route"):
        pb, pt, pqw, pcap, overflow = build_batched_pairs(
            cand_block, cand_valid, cand_q,
            cand_w.astype(jnp.float32), tfirst, tcount, n_tiles, b,
            max_pairs, cand_cap=cand_cap)

    # pad the query batch to the accumulator quantum (padding queries
    # get scale 1.0 — their zero accumulator masks them to -inf anyway)
    bp = -(-b // max(q_pad, 1)) * max(q_pad, 1)
    qscale_p = qscale
    if bp != b:
        pqw = jnp.pad(pqw, ((0, 0), (0, bp - b)))
        qscale_p = jnp.pad(qscale, (0, bp - b), constant_values=1.0)

    if isinstance(index, PackedCsrIndex):
        vals, ids = fused_topk_packed_pallas(
            index.packed, index.tf_pairs, pb, pt, pqw, pcap,
            index.block_bits[pb], index.block_base[pb],
            index.block_count[pb], row, index.docs.rank,
            qscale_p, num_docs, block, k_tile, rank_blend=rank_blend,
            tile=tile, reducer=reducer, interpret=_interp(backend),
            ranking=ranking)
    else:
        vals, ids = fused_topk_blocked_pallas(
            index.block_docs, index.block_tfs, pb, pt, pqw, pcap,
            row, index.docs.rank, qscale_p, num_docs, k_tile,
            rank_blend=rank_blend, tile=tile, reducer=reducer,
            interpret=_interp(backend), ranking=ranking)
    return vals[:b], ids[:b], overflow


# ---------------------------------------------------------------------------
# per-segment engines for the segmented live index (core/live_index.py)
# ---------------------------------------------------------------------------
#
# One sealed segment == one BlockedIndex padded to a static size class
# (layouts.pad_blocked_to_class).  These module-level jitted entry points
# take the segment as a pytree ARGUMENT (not a captured constant), so a
# freshly sealed segment of an already-warm class reuses the compiled
# executable — the live index's recompile-avoidance contract.  Each
# returns per-tile candidate lists of FINAL scores with GLOBAL doc ids
# (segment-local ids shifted by the traced ``doc_base`` scalar), merged
# host-side by ``distributed.topk.merge_topk_candidates_host``.
#
# ``idf_w`` carries GLOBAL query weights (live df over live docs,
# computed on the host by the index's ranking) — a segment never scores
# with its local df, so the multi-segment ranking matches a from-scratch
# rebuild exactly.  Slots whose term is absent from THIS segment still
# count in the query's scale (it is a property of the query, not the
# segment) but gate no posting blocks.  ``ranking`` is static: each
# ranking compiles its own engine (BM25's walk is ``pair_walk_bm25``).


@functools.partial(jax.jit, static_argnames=(
    "k_tile", "cap", "max_pairs", "rank_blend", "tile", "backend",
    "q_pad", "reducer", "ranking"))
def fused_segment_topk(index: BlockedIndex | PackedCsrIndex,
                       query_hashes: Array, idf_w: Array, qscale: Array,
                       doc_base: Array, *, k_tile: int,
                       cap: int, max_pairs: int, rank_blend: float = 0.0,
                       tile: int = TILE, backend: Backend = "pallas",
                       q_pad: int = Q_PAD, reducer: str = "successive",
                       ranking: Ranking = TFIDF_COSINE):
    """Candidate engine over one segment: fused decode-and-score kernel
    with in-kernel per-tile top-k (tombstones ride in as a doc row of
    0).

    Accepts either sealed-segment layout — HOR blocks (``seal_layout=
    "hor"``) or delta+bit-packed blocks (``"packed"``); the pytree
    STRUCTURE is part of the jit key, so compilations key on
    ``(size_class, layout)``: the two layouts compile separately but
    segments of one layout still share warm size-class entries.  The
    sharded serving tier applies the same keying to whole stacks
    (``distributed.retrieval.stack_segment_shards`` groups segments on
    ``(size_class, layout)`` and memoizes the compiled stack scorer)."""
    present = query_hashes != 0
    tids = jnp.where(present, index.lookup_terms(query_hashes), -1)
    vals, ids, overflow = fused_batched_topk(
        index, tids, idf_w, cap, k=k_tile, rank_blend=rank_blend,
        max_pairs=max_pairs, tile=tile, k_tile=k_tile, backend=backend,
        q_pad=q_pad, reducer=reducer, qscale=qscale, ranking=ranking)
    gids = jnp.where(ids >= 0, ids + doc_base, -1)
    return vals, gids, overflow


@functools.partial(jax.jit, static_argnames=(
    "k_tile", "cap", "max_pairs", "rank_blend", "tile", "backend",
    "q_pad"))
def fused_segment_dense_topk(index: BlockedIndex | PackedCsrIndex,
                             query_hashes: Array, idf_w: Array,
                             qnorm: Array, doc_base: Array, *, k_tile: int,
                             cap: int, max_pairs: int,
                             rank_blend: float = 0.0, tile: int = TILE,
                             backend: Backend = "pallas",
                             q_pad: int = Q_PAD):
    """Dense engine over one segment (PR-1 tail): full local score rows,
    then the jnp mirror of the per-tile candidate reduction."""
    present = query_hashes != 0
    tids = jnp.where(present, index.lookup_terms(query_hashes), -1)
    scores, overflow = fused_batched_scores(
        index, tids, idf_w, cap, max_pairs=max_pairs, tile=tile,
        backend=backend, q_pad=q_pad)
    final = final_scores(scores, index.docs.norm, index.docs.rank, qnorm,
                         rank_blend)
    vals, ids = extract_tile_candidates(final, tile, k_tile)
    gids = jnp.where(ids >= 0, ids + doc_base, -1)
    return vals, gids, overflow


def banded_pairs_budgets(index: BandedCsrIndex, num_queries: int,
                         num_terms: int, cap: int, tile: int = TILE
                         ) -> tuple[int, int, int, int]:
    """Per-band static pair budgets of a served ``[num_queries,
    num_terms]`` batch over a banded segment: each band is its own
    fused-kernel launch with its own routing-pair buffer, bounded like
    any segment by ``default_max_pairs`` (a term lives in one band, so
    the batch's term slots bound each band).  Returns the packed and
    HOR budgets and the per-band caps they were sized for."""
    cap_p = min(int(cap), max(index.packed.max_posting_len, 1))
    cap_h = min(int(cap), max(index.hor.max_posting_len, 1))
    return (default_max_pairs(index.packed, num_queries, num_terms, cap_p,
                              tile),
            default_max_pairs(index.hor, num_queries, num_terms, cap_h,
                              tile),
            cap_p, cap_h)


@functools.partial(jax.jit, static_argnames=(
    "k_tile", "cap_packed", "cap_hor", "max_pairs_packed", "max_pairs_hor",
    "rank_blend", "tile", "backend", "q_pad"))
def fused_segment_banded_topk(index: BandedCsrIndex, query_hashes: Array,
                              idf_w: Array, qnorm: Array, doc_base: Array,
                              *, k_tile: int,
                              cap_packed: int, cap_hor: int,
                              max_pairs_packed: int, max_pairs_hor: int,
                              rank_blend: float = 0.0, tile: int = TILE,
                              backend: Backend = "pallas",
                              q_pad: int = Q_PAD):
    """Engine over one BANDED segment: one fused dense-score launch per
    band (packed band with its band-local stride, HOR tail), band
    partials summed, then the shared scoring tail + per-tile candidate
    reduction.

    One term lookup serves both bands (they share the sorted_hash
    buffer; the band a term does NOT live in holds an empty block range
    for it, so it gates no pairs there).  Scores are additive over
    terms, so ``acc_packed + acc_hor`` is the whole-segment accumulator
    — and because every term contributes through exactly one band, a
    doc's partial in the other band is exactly 0.0, keeping the sum
    bit-identical to a single-layout engine whenever each doc's terms
    are band-pure (the engineered parity tests pin this; mixed docs get
    the same float regrouping tolerance as the term-sharded psum).

    The pytree structure keys compilation on the PAIR of band size
    classes, so warm-class rebuilds reuse the executable — the same
    memoization contract as ``fused_segment_topk``."""
    present = query_hashes != 0
    tids = jnp.where(present, index.packed.lookup_terms(query_hashes), -1)
    acc_p, ov_p = fused_batched_scores(
        index.packed, tids, idf_w, cap_packed, max_pairs=max_pairs_packed,
        tile=tile, backend=backend, q_pad=q_pad)
    acc_h, ov_h = fused_batched_scores(
        index.hor, tids, idf_w, cap_hor, max_pairs=max_pairs_hor,
        tile=tile, backend=backend, q_pad=q_pad)
    scores = acc_p + acc_h
    final = final_scores(scores, index.docs.norm, index.docs.rank, qnorm,
                         rank_blend)
    vals, ids = extract_tile_candidates(final, tile, k_tile)
    gids = jnp.where(ids >= 0, ids + doc_base, -1)
    return vals, gids, ov_p + ov_h


@functools.partial(jax.jit, static_argnames=(
    "k_tile", "cap", "rank_blend", "tile", "ranking"))
def jnp_segment_topk(index, query_hashes: Array, idf_w: Array,
                     qscale: Array, doc_base: Array, *, k_tile: int, cap: int,
                     rank_blend: float = 0.0, tile: int = TILE,
                     ranking: Ranking = TFIDF_COSINE):
    """Pure-jnp oracle engine over one segment (gather + scatter-add),
    reduced to the same per-tile candidate lists as the fused kernels."""
    from repro.core.query import accumulate_scores
    num_docs = index.docs.num_docs
    row = ranking.doc_row(index.docs)

    def one(qh, w):
        present = qh != 0
        tids = jnp.where(present, index.lookup_terms(qh), -1)
        # ascending term id: the order the fused engines add a doc's
        # per-term contributions in (routing pairs are block-sorted)
        order = jnp.argsort(jnp.where(tids >= 0, tids,
                                      jnp.iinfo(jnp.int32).max))
        tids, w = tids[order], w[order]
        d, tf, valid = index.gather_postings(tids, cap)
        drow = row[jnp.maximum(d, 0)] if ranking.saturates else None
        return accumulate_scores(d, ranking.posting(tf, drow) * w[:, None],
                                 valid, num_docs)

    scores = jax.vmap(one)(query_hashes, idf_w)
    final = final_scores(scores, row, index.docs.rank, qscale,
                         rank_blend, ranking)
    vals, ids = extract_tile_candidates(final, tile, k_tile)
    gids = jnp.where(ids >= 0, ids + doc_base, -1)
    return vals, gids, jnp.int32(0)


@functools.partial(jax.jit, static_argnames=("k_tile", "cap", "tile"))
def jnp_segment_conjunctive(index, query_hashes: Array, idf_w: Array,
                            needed: Array, doc_base: Array, *, k_tile: int,
                            cap: int, tile: int = TILE):
    """AND-semantics membership counts + scores over one segment for a
    SINGLE query; a doc lives in exactly one segment, so its local count
    is its global count.  Returns (vals, gids, truncated_terms) where
    ``truncated_terms`` counts terms whose LOCAL posting list exceeds
    ``cap`` — the live index SUMS this across segments (the stats-
    plumbing fix: truncation in any segment is surfaced, not just the
    last one scored)."""
    from repro.core.query import accumulate_counts, accumulate_scores
    num_docs = index.docs.num_docs
    present = query_hashes != 0
    tids = jnp.where(present, index.lookup_terms(query_hashes), -1)
    df_local = index.term_df(tids)
    d, tf, valid = index.gather_postings(tids, cap)
    scores = accumulate_scores(d, tf * idf_w[:, None], valid, num_docs)
    counts = accumulate_counts(d, valid, num_docs)
    truncated = jnp.sum(((df_local > cap) & (tids >= 0)).astype(jnp.int32))
    ok = counts >= needed
    final = jnp.where(ok & (index.docs.norm > 0),
                      scores / jnp.maximum(index.docs.norm, 1e-12),
                      -jnp.inf)
    vals, ids = extract_tile_candidates(final[None], tile, k_tile)
    gids = jnp.where(ids[0] >= 0, ids[0] + doc_base, -1)
    return vals[0], gids, truncated


def segment_scorer_cache_sizes() -> dict:
    """jit-cache sizes of the per-segment engines — the live index's
    churn test asserts these stop growing once every size class is warm
    (new compilations would mean the size-class contract broke)."""
    return {
        "fused_segment_topk": fused_segment_topk._cache_size(),
        "fused_segment_dense_topk": fused_segment_dense_topk._cache_size(),
        "fused_segment_banded_topk":
            fused_segment_banded_topk._cache_size(),
        "jnp_segment_topk": jnp_segment_topk._cache_size(),
        "jnp_segment_conjunctive": jnp_segment_conjunctive._cache_size(),
    }


# ---------------------------------------------------------------------------
# packed-posting decode
# ---------------------------------------------------------------------------


def unpack_postings(index: PackedCsrIndex,
                    backend: Backend = "pallas") -> Array:
    """Decode ALL blocks of a PackedCsrIndex -> doc ids i32[NB, block]."""
    if backend == "xla":
        return ref.ref_unpack_blocks(index.packed, index.block_bits,
                                     index.block_base, index.block_count,
                                     index.block)
    return unpack_blocks_pallas(index.packed, index.block_bits,
                                index.block_base, index.block_count,
                                index.block, interpret=_interp(backend))


# ---------------------------------------------------------------------------
# embedding bag / PNA aggregation / attention
# ---------------------------------------------------------------------------


def embedding_bag(table: Array, indices: Array, tile_b: int = 256,
                  backend: Backend = "xla") -> Array:
    if backend == "xla":
        return ref.ref_embedding_bag(table, indices)
    return embedding_bag_pallas(table, indices, tile_b=tile_b,
                                interpret=_interp(backend))


def pna_multi_agg(feats: Array, nbr: Array, tile_n: int = 128,
                  backend: Backend = "xla") -> Array:
    if backend == "xla":
        return ref.ref_pna_multi_agg(feats, nbr)
    return pna_multi_agg_pallas(feats, nbr, tile_n=tile_n,
                                interpret=_interp(backend))


def attention(q: Array, k: Array, v: Array, causal: bool = True,
              window: int = 0, backend: Backend = "xla",
              block_q: int = 128, block_k: int = 128) -> Array:
    if backend == "xla":
        return ref.ref_attention(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=_interp(backend))
