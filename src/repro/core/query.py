"""Query evaluation — the paper's §3.7 elementary queries over any layout.

The paper decomposes vector-space evaluation into three elementary
queries (Table 3):

  q_word : term name -> (term id, df)         [lookup phase]
  q_occ  : term id   -> posting list (doc,tf) [gather phase]
  q_doc  : doc ids   -> (norm, rank)          [doc-metadata phase]

Every layout in ``core/layouts.py`` exposes ``lookup_terms`` /
``term_df`` / ``gather_postings``; for COR/HOR/packed the lookup is fused
into the occurrence structure (the paper's "one fewer query").  This
module implements the shared scoring core (tf-idf cosine + static-rank
blend), top-k, and batched evaluation.  It is also the pure-jnp oracle
that the Pallas scoring kernel is validated against.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.ranking import TFIDF_COSINE, Ranking

Array = jax.Array


class QueryResult(NamedTuple):
    doc_ids: Array    # i32[k]   (-1 where fewer than k hits)
    scores: Array     # f32[k]


def idf(df: Array, num_docs: int) -> Array:
    """idf = ln(1 + D/df); 0 where the term is absent (df == 0)."""
    safe = jnp.maximum(df, 1)
    return jnp.where(df > 0, jnp.log1p(num_docs / safe.astype(jnp.float32)),
                     0.0)


def dedup_query_hashes(query_hashes: Array) -> Array:
    """Zero out repeated term hashes within each query (keep the first).

    A term name appearing in two slots of the padded query vector must
    contribute ONCE: the gather phase reads one posting list per slot,
    so without dedup the term's tf·idf weight is double-counted by every
    engine and the query norm inflates.  Works on [..., T]; 0 (empty
    slot) is never treated as a duplicate.
    """
    t = query_hashes.shape[-1]
    eq = query_hashes[..., :, None] == query_hashes[..., None, :]
    earlier = jnp.tril(jnp.ones((t, t), jnp.bool_), k=-1)
    dup = jnp.any(eq & earlier, axis=-1) & (query_hashes != 0)
    return jnp.where(dup, 0, query_hashes)


def final_scores(scores: Array, row: Array, rank: Array, qscale: Array,
                 rank_blend: float,
                 ranking: Ranking = TFIDF_COSINE) -> Array:
    """Batched q_doc scoring tail of ``ranking`` (``core/ranking.py``);
    deleted (row 0) and zero-score docs -> -inf.

    scores f32[B, D], row/rank f32[D], qscale f32[B].  The fused
    candidate kernels apply the SAME tail per resident tile
    (``fused_decode_score._final_from_acc``), so candidate values are
    bit-identical to this dense reference.
    """
    return ranking.tail(scores, row[None, :], rank[None, :],
                        qscale[:, None], rank_blend)


def accumulate_scores(doc_ids: Array, weights: Array, valid: Array,
                      num_docs: int) -> Array:
    """Scatter-add posting weights into a dense per-document accumulator.

    doc_ids/weights/valid: [T, cap].  Invalid postings are routed to a
    trash row (index num_docs).  Returns f32[num_docs].

    One scatter per term row, in row order: a row's doc ids are
    distinct, so each doc's sum is taken in row order on every backend
    (a single scatter over all rows leaves the order of a doc's
    duplicate updates to the compiler).
    """
    docs = jnp.where(valid, doc_ids, num_docs)
    w = jnp.where(valid, weights, 0.0)
    acc = jnp.zeros((num_docs + 1,), jnp.float32)
    for t in range(docs.shape[0]):
        acc = acc.at[docs[t]].add(w[t], mode="drop")
    return acc[:num_docs]


def accumulate_counts(doc_ids: Array, valid: Array, num_docs: int) -> Array:
    """Exact per-document membership counts (int32 accumulator).

    AND-filtering must COUNT postings, and float32 accumulation loses
    integer exactness past 2**24 — membership counts are integers, so
    they are accumulated as integers.  Returns i32[num_docs].
    """
    flat_docs = jnp.where(valid, doc_ids, num_docs).reshape(-1)
    ones = jnp.where(valid, 1, 0).reshape(-1).astype(jnp.int32)
    acc = jnp.zeros((num_docs + 1,), jnp.int32)
    acc = acc.at[flat_docs].add(ones, mode="drop")
    return acc[:num_docs]


def score_query(index: Any, query_hashes: Array, k: int, cap: int,
                rank_blend: float = 0.0) -> QueryResult:
    """Evaluate one query (padded term-hash vector; 0 = empty slot).

    Implements the paper's three-phase evaluation: lookup -> gather ->
    doc metadata; ranks by cosine(q, d) (+ optional static-rank blend).
    """
    query_hashes = dedup_query_hashes(query_hashes)
    present = query_hashes != 0
    term_ids = index.lookup_terms(query_hashes)            # q_word
    term_ids = jnp.where(present, term_ids, -1)
    df = index.term_df(term_ids)
    num_docs = index.docs.num_docs
    idf_t = idf(df, num_docs)

    d, tf, valid = index.gather_postings(term_ids, cap)    # q_occ
    w = tf * idf_t[:, None]

    scores = accumulate_scores(d, w, valid, num_docs)

    # q_doc: norms + static rank for candidate docs (dense fetch here; the
    # distributed engine fetches only per-shard candidates).
    qnorm = jnp.sqrt(jnp.maximum(jnp.sum(idf_t * idf_t), 1e-12))
    final = final_scores(scores[None, :], index.docs.norm, index.docs.rank,
                         qnorm[None], rank_blend)[0]

    top_scores, top_docs = jax.lax.top_k(final, k)
    hit = jnp.isfinite(top_scores)
    return QueryResult(doc_ids=jnp.where(hit, top_docs, -1),
                       scores=jnp.where(hit, top_scores, 0.0))


def score_queries(index: Any, query_hashes: Array, k: int, cap: int,
                  rank_blend: float = 0.0) -> QueryResult:
    """Batched evaluation: query_hashes u32[B, T]."""
    fn = functools.partial(score_query, index, k=k, cap=cap,
                           rank_blend=rank_blend)
    return jax.vmap(lambda q: fn(query_hashes=q))(query_hashes)


def fused_score_queries(index: Any, query_hashes: Array, k: int, cap: int,
                        rank_blend: float = 0.0,
                        max_pairs: int | None = None,
                        backend: str = "pallas",
                        mode: str = "candidates",
                        tune: Any = None):
    """Batched evaluation through the fused decode-and-score Pallas
    engine (one HBM pass over the shared posting blocks for the whole
    batch).  Requires a BlockedIndex or PackedCsrIndex.

    ``mode="candidates"`` (default) extracts per-tile top-k candidates
    INSIDE the kernel — only O(B * n_tiles * k_tile) candidates reach
    HBM, merged here by the pure ``merge_topk_candidates`` tier;
    ``mode="dense"`` is the PR-1 engine (dense [B, num_docs] scores +
    host-side top_k), kept as the byte-accounting reference.

    Returns (QueryResult, stats) where stats carries the routing
    ``pair_overflow`` counter — nonzero means postings were DROPPED
    because ``max_pairs`` was undersized, never silently.

    ``tune`` is an optional ``kernels.autotune.TuneConfig``; ``None``
    resolves the ACTIVE tuning table for this index's (backend,
    size_class, layout) — which is the historical default geometry
    while the table is empty.
    """
    from repro.kernels import autotune, ops   # (late: avoids import cycle)
    from repro.distributed.topk import merge_topk_candidates

    if mode not in ("candidates", "dense"):
        raise ValueError(f"unknown fused-engine mode: {mode!r}")
    if tune is None:
        tune = autotune.lookup(backend, int(index.docs.num_docs),
                               autotune.layout_of(index))
    query_hashes = dedup_query_hashes(query_hashes)
    present = query_hashes != 0                            # [B, T]
    term_ids = jnp.where(present, index.lookup_terms(query_hashes), -1)
    df = index.term_df(term_ids)
    num_docs = index.docs.num_docs
    idf_t = idf(df, num_docs)

    if mode == "candidates":
        cand_v, cand_i, overflow = ops.fused_batched_topk(
            index, term_ids, idf_t, cap, k, rank_blend=rank_blend,
            max_pairs=max_pairs, backend=backend, tile=tune.tile,
            k_tile=tune.resolve_k_tile(k), q_pad=tune.q_pad,
            reducer=tune.reducer)
        ops.warn_on_overflow(overflow, "fused engine")
        top_scores, top_docs = merge_topk_candidates(cand_v, cand_i, k)
    else:
        scores, overflow = ops.fused_batched_scores(
            index, term_ids, idf_t, cap, max_pairs=max_pairs,
            backend=backend, tile=tune.tile, q_pad=tune.q_pad)
        ops.warn_on_overflow(overflow, "fused engine")
        # identical scoring tail to score_query (the parity oracle)
        qnorm = jnp.sqrt(jnp.maximum(jnp.sum(idf_t * idf_t, axis=1), 1e-12))
        final = final_scores(scores, index.docs.norm, index.docs.rank,
                             qnorm, rank_blend)
        top_scores, top_docs = jax.lax.top_k(final, k)
    hit = jnp.isfinite(top_scores)
    result = QueryResult(doc_ids=jnp.where(hit, top_docs, -1),
                         scores=jnp.where(hit, top_scores, 0.0))
    return result, {"pair_overflow": overflow}


def make_scorer(index: Any, k: int, cap: int, rank_blend: float = 0.0,
                engine: str = "jnp", max_pairs: int | None = None,
                backend: str = "pallas", mode: str = "candidates",
                return_stats: bool = False, tune: Any = None
                ) -> Callable[[Array], QueryResult]:
    """jit-compiled batched scorer with the index captured as constants.

    ``engine="jnp"`` is the dense pure-jnp oracle; ``engine="pallas"``
    dispatches the fused batched decode-and-score kernel (BlockedIndex /
    PackedCsrIndex only) — same ranked results, one HBM pass, and (with
    the default ``mode="candidates"``) in-kernel per-tile top-k so the
    dense score array never reaches HBM.
    ``backend`` tunes the fused engine's lowering ("pallas" auto /
    "pallas-tpu" / "xla" plain-HLO with the same block dedup).  With
    ``return_stats=True`` the scorer returns (QueryResult, stats).

    ``tune``: explicit ``kernels.autotune.TuneConfig`` kernel geometry;
    ``None`` resolves the ACTIVE tuning table at trace time (an empty
    table yields the historical defaults).  The resolved geometry is
    captured in the jitted scorer — swap the active table BEFORE
    building a scorer, not after.
    """
    if engine not in ("jnp", "pallas"):
        raise ValueError(f"unknown engine: {engine!r}")
    if mode not in ("candidates", "dense"):
        raise ValueError(f"unknown fused-engine mode: {mode!r}")
    from repro.core.live_index import SegmentedIndex  # avoid import cycle
    if isinstance(index, SegmentedIndex):
        # multi-segment path: one fused candidate launch per sealed
        # segment + static-shape delta scoring + host candidate merge
        # (the index handles its own per-segment jit caching)
        if max_pairs is not None:
            raise ValueError(
                "max_pairs is not configurable for a SegmentedIndex — "
                "each sealed segment carries its own exact (size-class "
                "quantized) route_pairs_max budget")
        def live_scorer(query_hashes: Array):
            return index.topk(query_hashes, k, cap=cap,
                              rank_blend=rank_blend, engine=engine,
                              mode=mode, backend=backend,
                              return_stats=return_stats, tune=tune)
        return live_scorer
    if engine == "pallas":
        from repro.core.layouts import BlockedIndex, PackedCsrIndex
        if not isinstance(index, (BlockedIndex, PackedCsrIndex)):
            raise TypeError(
                f"engine='pallas' needs a BlockedIndex or PackedCsrIndex, "
                f"got {type(index).__name__}")

    @jax.jit
    def scorer(query_hashes: Array):
        if engine == "pallas":
            result, stats = fused_score_queries(
                index, query_hashes, k=k, cap=cap, rank_blend=rank_blend,
                max_pairs=max_pairs, backend=backend, mode=mode, tune=tune)
        else:
            result = score_queries(index, query_hashes, k=k, cap=cap,
                                   rank_blend=rank_blend)
            stats = {"pair_overflow": jnp.int32(0)}
        return (result, stats) if return_stats else result
    return scorer


# ---------------------------------------------------------------------------
# adaptive routing budgets (fused engine's max_pairs, learned online)
# ---------------------------------------------------------------------------


def _pow2_at_least(n: int, floor: int = 8) -> int:
    """Power-of-two budget quantizer — the ONE geometric size-class
    quantizer (layouts.size_class) at growth 2, so budget quantization
    and segment size classes can never silently diverge."""
    from repro.core.layouts import size_class
    return size_class(n, base=floor, growth=2)


class AdaptiveRoutingBudget:
    """Per-``n_terms`` routing-pair budgets learned from the fused
    engine's overflow counter and a rolling query-stream sample.

    The static ``max_pairs`` budget trades compile-time shape against
    dropped postings: too small and the engine overflows (surfaced, but
    work is lost), too large and every launch pays for routing slots the
    workload never fills.  Instead of the worst-case build-time bound,
    this tracks the OBSERVED demand per query width: when a batch
    overflows, the true demand is exactly ``budget + overflow`` (the
    counter reports dropped pairs), so one growth step reaches a
    sufficient budget; a rolling window of recent demands lets quiet
    buckets shrink back.  Budgets quantize to powers of two so the
    compile set stays logarithmic in demand (each distinct value is one
    jit signature).
    """

    def __init__(self, initial: int = 64, window: int = 64,
                 shrink_ratio: int = 4):
        self.initial = int(initial)
        self.window = int(window)
        self.shrink_ratio = int(shrink_ratio)
        self._budgets: dict[int, int] = {}
        self._demands: dict[int, list] = {}
        self.overflows = 0          # batches that overflowed (telemetry)

    def budget(self, n_terms: int) -> int:
        return self._budgets.setdefault(
            int(n_terms), _pow2_at_least(self.initial))

    def observe(self, n_terms: int, used_budget: int,
                overflow: int) -> None:
        """Record one batch: ``overflow`` pairs were dropped beyond
        ``used_budget``, so the exact demand was their sum."""
        n_terms = int(n_terms)
        demand = int(used_budget) + int(overflow)
        hist = self._demands.setdefault(n_terms, [])
        hist.append(demand)
        del hist[:-self.window]
        cur = self.budget(n_terms)
        if overflow > 0:
            self.overflows += 1
            # grow past the exact demand by one doubling of headroom so
            # batch-to-batch demand jitter doesn't overflow again at the
            # next power-of-two boundary
            self._budgets[n_terms] = _pow2_at_least(demand) * 2
        elif (len(hist) >= self.window and
              _pow2_at_least(max(hist)) * self.shrink_ratio <= cur):
            # sustained quiet: shrink toward the sampled demand (one
            # headroom doubling), at most one recompile per window
            self._budgets[n_terms] = _pow2_at_least(max(hist)) * 2


def make_adaptive_scorer(index: Any, k: int, cap: int,
                         budget: AdaptiveRoutingBudget | None = None,
                         **scorer_kw):
    """Fused-engine scorer whose ``max_pairs`` follows the workload.

    Batches are bucketed by their widest query (unique present terms);
    each bucket's budget starts small and converges via the overflow
    counter — an overflowing workload reaches zero overflow within a
    growth step per bucket (regression-tested).  Returns
    ``fn(query_hashes) -> (QueryResult, stats)`` with the budget object
    on ``fn.budget`` for introspection.
    """
    budget = budget if budget is not None else AdaptiveRoutingBudget()
    scorers: dict[int, Callable] = {}

    def scorer(query_hashes: Array):
        import numpy as np
        qh = np.asarray(query_hashes)
        deduped = np.asarray(dedup_query_hashes(jnp.asarray(qh)))
        n_terms = max(int((deduped != 0).sum(axis=-1).max()), 1)
        mp = budget.budget(n_terms)
        if mp not in scorers:
            scorers[mp] = make_scorer(index, k=k, cap=cap,
                                      engine="pallas", max_pairs=mp,
                                      return_stats=True, **scorer_kw)
        result, stats = scorers[mp](query_hashes)
        budget.observe(n_terms, mp, int(stats["pair_overflow"]))
        return result, stats

    scorer.budget = budget
    return scorer


# ---------------------------------------------------------------------------
# Boolean / membership utilities (exercise document-based access paths)
# ---------------------------------------------------------------------------


def conjunctive_filter(index: Any, query_hashes: Array, k: int,
                       cap: int) -> tuple[QueryResult, dict]:
    """AND semantics: docs must contain every present query term.

    Duplicate hashes are deduplicated first so ``needed`` counts UNIQUE
    present terms (a repeated slot used to inflate both the membership
    counts and the threshold, and to double-count the tf·idf weight).

    Returns (QueryResult, stats).  ``stats["truncated_terms"]`` counts
    present terms whose posting list is LONGER than ``cap``: the gather
    phase drops their tail postings, so membership can be undercounted
    and true AND matches silently lost — like the fused engine's
    ``pair_overflow``, the truncation is surfaced instead of returning
    a silently wrong result (re-run with ``cap >= max df`` for exact
    AND semantics).
    """
    query_hashes = dedup_query_hashes(query_hashes)
    present = query_hashes != 0
    term_ids = jnp.where(present, index.lookup_terms(query_hashes), -1)
    df = index.term_df(term_ids)
    num_docs = index.docs.num_docs
    d, tf, valid = index.gather_postings(term_ids, cap)
    idf_t = idf(df, num_docs)
    w = tf * idf_t[:, None]
    scores = accumulate_scores(d, w, valid, num_docs)
    counts = accumulate_counts(d, valid, num_docs)
    needed = jnp.sum(present.astype(jnp.int32))
    truncated = jnp.sum(((df > cap) & (term_ids >= 0)).astype(jnp.int32))
    ok = counts >= needed
    final = jnp.where(ok & (index.docs.norm > 0),
                      scores / jnp.maximum(index.docs.norm, 1e-12), -jnp.inf)
    top_scores, top_docs = jax.lax.top_k(final, k)
    hit = jnp.isfinite(top_scores)
    result = QueryResult(doc_ids=jnp.where(hit, top_docs, -1),
                         scores=jnp.where(hit, top_scores, 0.0))
    from repro.kernels import ops   # (late: avoids import cycle)
    ops.record_truncated(truncated)
    return result, {"truncated_terms": truncated}
