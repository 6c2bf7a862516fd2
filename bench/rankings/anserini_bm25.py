"""Anserini's BM25 ranking: its plain reference, its part of the
snapshot and the check of the restored index.

The parameters are Anserini's BM25 baseline for MS MARCO passage
ranking (its ``msmarco-passage`` regression, "BM25 (default
parameters)"): k1 = 0.9, b = 0.4.  The harness hands a ranking module
no configuration, so they live here, and name the module; the
configuration restates them.

For a query of distinct terms ``t`` over the live shard of ``N``
documents,

    idf(t)    = ln(1 + (N - df(t) + 0.5) / (df(t) + 0.5))
    K(d)      = k1 (1 - b + b dl(d) / avgdl)
    score(d)  = sum_t idf(t) tf(d, t) (k1 + 1) / (tf(d, t) + K(d))

over the documents that hold at least one query term, best ``k``
first.  ``dl(d)`` is the document's token count (the sum of its tfs),
exact, and ``avgdl`` the mean over the shard; ``N``, ``df`` and
``avgdl`` are the shard's own statistics, as each shard of a
``query_then_fetch`` deployment scores.  The ``(k1 + 1)`` factor, which
Lucene 8+ drops, is kept (it scales every score alike); Lucene's
one-byte lossy length is not modelled.  No static-rank blend.

``Reference`` computes that from the benchmark's own corpus in float64,
with numpy alone: it imports nothing of the program and takes nothing it
has made.  ``precision="bfloat16"`` rounds every stored and computed
quantity to bfloat16 instead: the control, which has to fail.
"""
from __future__ import annotations

import importlib.util
import json

import numpy as np

from bench.reference import ROUNDING, Answer

K1 = 0.9
B = 0.4


def _meta() -> dict:
    return {"name": "bm25", "k1": K1, "b": B}


def snapshot_part(corpus) -> tuple[dict, dict]:
    """(arrays, meta keys) the ranking adds to the program's snapshot:
    the ranking in the meta, from which the program's restore builds a
    BM25 index (document lengths are the sums of the stored tfs).
    Refuses at once a program that has no rankings to restore."""
    if importlib.util.find_spec("repro.core.ranking") is None:
        raise RuntimeError("the program under test has no ranking seam "
                           "(repro.core.ranking): it cannot serve bm25")
    return {}, {"ranking": _meta()}


def after_restore(index) -> None:
    """Refuses an index that does not rank by this BM25, and prints the
    statistics of the epoch it will serve."""
    ranking = getattr(index, "ranking", None)
    got = (getattr(ranking, "name", None), getattr(ranking, "k1", None),
           getattr(ranking, "b", None))
    if got != ("bm25", K1, B):
        raise RuntimeError(f"the restored index ranks by {got}, not "
                           f"bm25 with k1={K1}, b={B}")
    view = index.view()
    print(json.dumps({"phase": "ranking", **_meta(),
                      "live_docs": view.live_docs,
                      "live_tokens": view.live_tokens,
                      "avgdl": view.avgdl}), flush=True)


class Reference:
    def __init__(self, corpus, needed_terms, precision: str = "float64"):
        r = ROUNDING[precision]
        self.round = r
        n = corpus.num_docs
        df = corpus.df().astype(np.float64)
        self.num_docs = n
        self.idf = r(np.where(df > 0, np.log1p((n - df + 0.5) / (df + 0.5)),
                              0.0))
        doc = corpus.doc_of()
        dl = np.bincount(doc, weights=corpus.tfs, minlength=n)
        avgdl = r(dl.sum() / n)
        self.k = r(K1 * r(1.0 - B + r(B * r(r(dl) / avgdl))))
        want = np.zeros(corpus.vocab, bool)
        want[np.asarray(list(needed_terms), np.int64)] = True
        keep = want[corpus.terms]
        terms = corpus.terms[keep]
        order = np.argsort(terms, kind="stable")
        self._docs = doc[keep][order]
        self._tfs = corpus.tfs[keep][order].astype(np.float64)
        self._ptr = np.searchsorted(terms[order],
                                    np.arange(corpus.vocab + 1))

    def answer(self, terms, k: int) -> Answer:
        r = self.round
        terms = np.asarray(terms, np.int64)
        parts = []
        for t in terms:
            s = slice(self._ptr[t], self._ptr[t + 1])
            tf, d = self._tfs[s], self._docs[s]
            sat = r(r(tf * (K1 + 1.0)) / r(tf + self.k[d]))
            parts.append((d, r(sat * self.idf[t])))
        docs = np.concatenate([d for d, _ in parts])
        ws = np.concatenate([w for _, w in parts])
        acc = r(np.bincount(docs, weights=ws, minlength=self.num_docs))
        cand = np.flatnonzero(acc > 0)
        sc = acc[cand]
        score = np.zeros(self.num_docs)
        score[cand] = sc
        n_hit = min(k, len(cand))
        part = (np.argpartition(-sc, n_hit - 1)[:n_hit]
                if 0 < n_hit < len(cand) else np.arange(n_hit))
        best = cand[part[np.lexsort((cand[part], -sc[part]))]]
        return Answer(ids=best, top=score[best], score=score)
