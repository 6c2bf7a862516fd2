"""The tf-idf cosine ranking, the program's own: its plain reference,
its part of the snapshot and the index's set-up after restore.

For a query of distinct terms ``t`` over the live collection of ``D``
documents,

    idf(t)    = ln(1 + D / df(t))
    score(d)  = sum_t tf(d, t) idf(t) / (|d| |q|)
    |d|       = sqrt(sum over every term u of d of (tf(d, u) idf(u))^2)
    |q|       = sqrt(sum_t idf(t)^2)

over the documents that hold at least one query term, best ``k`` first:
raw tf, no static-rank blend.  ``Reference`` computes that from the
benchmark's own corpus in float64, with numpy alone: it imports nothing
of the program and takes nothing it has made.  ``precision="bfloat16"``
rounds every stored and computed quantity to bfloat16 instead: the
control, which has to fail.
"""
from __future__ import annotations

import numpy as np

from bench.reference import ROUNDING, Answer


def snapshot_part(corpus) -> tuple[dict, dict]:
    """(arrays, meta keys) the ranking adds to the program's snapshot:
    none, since the program computes its norms after restore."""
    return {}, {}


def after_restore(index) -> None:
    """The program's cosine set-up: every live document's norm."""
    index.refresh_norms()


class Reference:
    def __init__(self, corpus, needed_terms, precision: str = "float64"):
        r = ROUNDING[precision]
        self.round = r
        n = corpus.num_docs
        df = corpus.df()
        self.num_docs = n
        self.idf = r(np.where(df > 0, np.log1p(n / np.maximum(df, 1)), 0.0))
        doc = corpus.doc_of()
        w = r(corpus.tfs * self.idf[corpus.terms])
        self.norm = r(np.sqrt(np.bincount(doc, weights=w * w,
                                          minlength=n)))
        want = np.zeros(corpus.vocab, bool)
        want[np.asarray(list(needed_terms), np.int64)] = True
        keep = want[corpus.terms]
        terms = corpus.terms[keep]
        order = np.argsort(terms, kind="stable")
        self._docs = doc[keep][order]
        self._tfs = corpus.tfs[keep][order]
        self._ptr = np.searchsorted(terms[order],
                                    np.arange(corpus.vocab + 1))

    def answer(self, terms, k: int) -> Answer:
        r = self.round
        terms = np.asarray(terms, np.int64)
        spans = [slice(self._ptr[t], self._ptr[t + 1]) for t in terms]
        docs = np.concatenate([self._docs[s] for s in spans])
        ws = np.concatenate([r(self._tfs[s] * self.idf[t])
                             for s, t in zip(spans, terms)])
        acc = r(np.bincount(docs, weights=ws, minlength=self.num_docs))
        qnorm = r(np.sqrt(np.sum(self.idf[terms] ** 2)))
        cand = np.flatnonzero(acc > 0)
        sc = r(acc[cand] / r(self.norm[cand] * qnorm))
        score = np.zeros(self.num_docs)
        score[cand] = sc
        n_hit = min(k, len(cand))
        part = (np.argpartition(-sc, n_hit - 1)[:n_hit]
                if 0 < n_hit < len(cand) else np.arange(n_hit))
        best = cand[part[np.lexsort((cand[part], -sc[part]))]]
        return Answer(ids=best, top=score[best], score=score)
