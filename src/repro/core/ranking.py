"""The ranking seam: how a live index turns postings into scores.

An index gets its ranking when it is built or restored
(``SegmentedIndex(ranking=...)``, the snapshot's meta); it is a fact of
the deployment, not a tuning knob.  One object defines all four parts
every engine needs, so the walk, the delta scan, ``LiveView.topk`` and
the jnp oracle take them from here and name no formula themselves:

* the query weights, on the host: ``query_weights(df, live_docs)`` ->
  (per-slot weight, per-query scale);
* the per-document row, on the host: ``doc_rows(...)``, pushed to the
  device in the segment's ``DocTable`` (``doc_table`` / ``doc_row``);
* the per-posting transform: ``posting(tf, row)``;
* the tail: ``tail(acc, row, rank, qscale, rank_blend)``.

``tfidf_cosine`` (the paper's, and the default)::

    idf(t)   = ln(1 + N / df(t))
    score(d) = sum_t tf(d, t) idf(t) / (|d| |q|)

with ``|d|`` the document's tf-idf norm (its row; 0 marks a deleted
document) and ``|q|`` the norm of the query's idf weights (its scale).

``bm25`` with parameters ``k1`` and ``b``::

    idf(t)   = ln(1 + (N - df(t) + 0.5) / (df(t) + 0.5))
    K(d)     = k1 (1 - b + b dl(d) / avgdl)
    score(d) = sum_t idf(t) tf(d, t) (k1 + 1) / (tf(d, t) + K(d))

The sum runs over the query's distinct terms.  ``N``, ``df`` and
``avgdl`` (live tokens over live documents) are the shard's live
statistics at the pinned epoch.  ``dl`` is the document's exact token
count, the sum of its tfs; Lucene stores it lossily in one byte, this
index does not.  The ``(k1 + 1)`` factor, which Lucene 8+ drops, is
kept: it scales every score alike and changes no order.  The row is
``K(d)``, 0 for a deleted document (a live one has ``K >= k1 (1 - b) >
0``), so a tombstone is the same "row is not positive" test under both
rankings.  The scale is 1: BM25's tail divides by nothing.

Under both, no static-rank blend is applied unless ``rank_blend`` asks,
and a document scores only if it holds a query term.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.layouts import DocTable

COSINE = "tfidf_cosine"
BM25 = "bm25"


@dataclasses.dataclass(frozen=True)
class Ranking:
    """One ranking and its parameters.  Hashable: the engines take it as
    a static argument, so each ranking compiles its own program."""
    name: str = COSINE
    k1: float = 0.0
    b: float = 0.0

    def __post_init__(self):
        if self.name == BM25:
            if not (self.k1 > 0 and 0 <= self.b < 1):
                raise ValueError(f"bm25 needs k1 > 0 and 0 <= b < 1, got "
                                 f"k1={self.k1}, b={self.b}")
        elif self.name == COSINE:
            if self.k1 or self.b:
                raise ValueError("tfidf_cosine takes no parameters")
        else:
            raise ValueError(f"unknown ranking {self.name!r}")

    @property
    def saturates(self) -> bool:
        """True for BM25: the per-posting value depends on the
        document's row, so a kernel must hold the row while it walks."""
        return self.name == BM25

    # -- meta (the snapshot's record) ----------------------------------

    def to_meta(self) -> dict:
        meta = {"name": self.name}
        if self.saturates:
            meta.update(k1=float(self.k1), b=float(self.b))
        return meta

    @classmethod
    def from_meta(cls, meta: dict | None) -> "Ranking":
        """A snapshot without a ranking (written before rankings were
        recorded) restores as tf-idf cosine."""
        if meta is None:
            return TFIDF_COSINE
        return cls(str(meta["name"]), float(meta.get("k1", 0.0)),
                   float(meta.get("b", 0.0)))

    # -- host side -------------------------------------------------------

    def query_weights(self, df: np.ndarray, live_docs: int):
        """Per-slot weights and per-query scales of dedup'd query rows,
        in float32 numpy: every server takes its weights from here, so
        a query scores with the same bits whatever batch it rides in.

        df i32[..., T] live global document frequencies per slot (0:
        absent or empty).  Returns (w f32[..., T], qscale f32[...])."""
        df = np.asarray(df)
        n = np.float32(live_docs)
        safe = np.maximum(df, 1).astype(np.float32)
        if self.saturates:
            half = np.float32(0.5)
            w = np.where(df > 0, np.log1p((n - safe + half) / (safe + half)),
                         np.float32(0)).astype(np.float32)
            return w, np.ones(w.shape[:-1], np.float32)
        w = np.where(df > 0, np.log1p(n / safe),
                     np.float32(0)).astype(np.float32)
        return w, query_norms(w)

    def doc_rows(self, *, live: np.ndarray, df: np.ndarray,
                 live_docs: int, dl: np.ndarray, live_tokens: int,
                 postings) -> np.ndarray:
        """f32[num_docs] rows of every allocated document.

        ``postings`` yields (global doc ids, term ids, tfs) of every
        stored posting, each document's in ascending term order;
        ``dl`` holds every document's token count.  Cosine: the tf-idf
        norm, summed in float64 in that order as the bulk builder does
        (bit-identical to a rebuild), 1e-12 for a live empty document.
        BM25: ``K(d)``.  A deleted document's row is 0 under both."""
        n_alloc = len(live)
        if self.saturates:
            avgdl = np.float32(live_tokens / live_docs if live_docs else 1.0)
            row = np.float32(self.k1) * (
                np.float32(1.0 - self.b)
                + np.float32(self.b) * (dl.astype(np.float32) / avgdl))
            row = row.astype(np.float32)
        else:
            idf64 = (np.log1p(live_docs / np.maximum(df, 1).astype(
                np.float64)) if len(df) else np.zeros(0))
            norm_sq = np.zeros(n_alloc, np.float64)
            for gdoc, terms, tfs in postings:
                wv = tfs * idf64[terms.astype(np.int64)]
                norm_sq += np.bincount(gdoc, weights=wv * wv,
                                       minlength=n_alloc)
            row = np.sqrt(norm_sq).astype(np.float32)
            row[row == 0] = 1e-12
        row[~live] = 0.0
        return row

    # -- device side -----------------------------------------------------

    def doc_table(self, row, rank):
        """The segment's device ``DocTable`` from its padded rows: the
        norm row under cosine, the ``length`` row (``K``) under BM25;
        neither allocates the other's."""
        if self.saturates:
            return DocTable(norm=None, rank=jnp.asarray(rank),
                            length=jnp.asarray(row))
        return DocTable(norm=jnp.asarray(row), rank=jnp.asarray(rank))

    def doc_row(self, docs):
        """The device row of a ``DocTable`` this ranking scores with."""
        row = docs.length if self.saturates else docs.norm
        if row is None:
            raise ValueError(f"the index's doc table holds no row for "
                             f"{self.name}")
        return row

    def posting(self, tf, row):
        """A posting's value before its query weight: raw tf under
        cosine; under BM25 the saturated ``tf (k1+1) / (tf + K)``, 0
        where tf is 0 (lanes with no posting, whatever their row)."""
        if not self.saturates:
            return tf
        return jnp.where(tf > 0, tf * jnp.float32(self.k1 + 1.0)
                         / (tf + row), 0.0)

    def tail(self, acc, row, rank, qscale, rank_blend: float):
        """Final scores from accumulated ones: ``acc`` [B, D], ``row`` /
        ``rank`` [1, D] rows, ``qscale`` a [B, 1] column.  A deleted
        (row 0) or zero-score document gets -inf."""
        live = row > 0
        if self.saturates:
            final = acc + rank_blend * rank
        else:
            final = acc / (jnp.maximum(row, 1e-12) * qscale)
            final = final + rank_blend * rank
        return jnp.where(live & (acc > 0), final, -jnp.inf)


TFIDF_COSINE = Ranking()


def bm25(k1: float, b: float) -> Ranking:
    return Ranking(BM25, float(k1), float(b))


def query_norms(w: np.ndarray) -> np.ndarray:
    """f32[...] norms of weight rows f32[..., T], summed slot by slot
    on the host (numpy's own reduction order depends on the shape)."""
    w = np.asarray(w, np.float32)
    sq = np.zeros(w.shape[:-1], np.float32)
    for t in range(w.shape[-1]):
        sq = sq + w[..., t] * w[..., t]
    return np.sqrt(np.maximum(sq, np.float32(1e-12))).astype(np.float32)
