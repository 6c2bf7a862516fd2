"""Plain reference of the served answer, and the comparison with it.

The semantics are the program's documented ranking: for a query of
distinct terms ``t`` over the live collection of ``D`` documents,

    idf(t)    = ln(1 + D / df(t))
    score(d)  = sum_t tf(d, t) idf(t) / (|d| |q|)
    |d|       = sqrt(sum over every term u of d of (tf(d, u) idf(u))^2)
    |q|       = sqrt(sum_t idf(t)^2)

over the documents that hold at least one query term, best ``k`` first.
This module computes that from the benchmark's own corpus in float64,
with numpy alone: it imports nothing of the program and takes nothing
it has made.  ``precision="bfloat16"`` rounds every stored and computed
quantity to bfloat16 instead: the control, which has to fail.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np


def _bf16(x):
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def _exact(x):
    return np.asarray(x, np.float64)


@dataclasses.dataclass
class Answer:
    ids: np.ndarray       # i64[n_hit], best first
    top: np.ndarray       # f64[n_hit]  their scores
    score: np.ndarray     # f64[D]      every document's score (0: no hit)

    def served(self, k: int) -> tuple[np.ndarray, np.ndarray]:
        """The answer as the server gives it: k ids (-1 past the hits)
        and their scores (0 there)."""
        ids, scores = np.full(k, -1), np.zeros(k)
        ids[:len(self.ids)], scores[:len(self.top)] = self.ids, self.top
        return ids, scores


class Reference:
    def __init__(self, corpus, needed_terms, precision: str = "float64"):
        r = {"float64": _exact, "bfloat16": _bf16}[precision]
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


def gap(ids, scores, want: Answer) -> float | None:
    """Widest gap, relative to the best reference score, between a
    served score and (a) the reference's score of the same document,
    (b) the reference's score at the same rank.  ``None`` when the
    answer is wrong in kind: another number of hits, an id out of
    range, or an id given twice."""
    ids = np.asarray(ids, np.int64)
    scores = np.asarray(scores, np.float64)
    n_hit = len(want.ids)
    got = ids[ids >= 0]
    if (len(got) != n_hit or np.any(ids[:n_hit] < 0)
            or np.any(got >= len(want.score))
            or len(np.unique(got)) != n_hit):
        return None
    if n_hit == 0:
        return 0.0
    s = scores[:n_hit]
    wide = max(np.max(np.abs(s - want.score[got])),
               np.max(np.abs(s - want.top)))
    return float(wide / want.top[0])


def compare(answers, reference: Reference, k: int) -> dict:
    """``answers``: [(terms, ids, scores)].  Returns the widest gap
    over the answers right in kind, and how many were wrong in kind."""
    widest, bad = 0.0, 0
    for terms, ids, scores in answers:
        g = gap(ids, scores, reference.answer(terms, k))
        if g is None:
            bad += 1
        else:
            widest = max(widest, g)
    return {"score_gap": widest, "bad_answers": bad}


def verdict(found: dict, unanswered: int, limits: dict) -> tuple[dict, bool]:
    """The numbers compared, each with its limit, and whether every one
    holds.  ``found`` is what ``compare`` returned; ``unanswered`` counts
    the requests with no answer; ``limits`` is the configuration's."""
    checks = {
        "score_gap": {"value": found["score_gap"],
                      "limit": float(limits["score_gap"])},
        "bad_answers": {"value": found["bad_answers"], "limit": 0},
        "unanswered": {"value": int(unanswered), "limit": 0},
    }
    return checks, all(c["value"] <= c["limit"] for c in checks.values())
