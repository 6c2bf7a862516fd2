"""What every ranking's reference shares: the answer it gives, the
precisions it computes in, and the comparison of served answers with it.

The reference itself, with its formulas, belongs to the configuration's
ranking (``bench/rankings/<ranking>.py``: a ``Reference(corpus,
needed_terms, precision)`` whose ``answer(terms, k)`` gives an
``Answer``).  ``ROUNDING`` maps each precision a reference takes to the
rounding it applies to every stored and computed quantity: ``float64``,
and ``bfloat16`` for the control, which has to fail.
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


ROUNDING = {"float64": _exact, "bfloat16": _bf16}


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


def compare(answers, reference, k: int) -> dict:
    """``answers``: [(terms, ids, scores)]; ``reference``: a ranking's
    ``Reference``.  Returns the widest gap over the answers right in
    kind, and how many were wrong in kind."""
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
