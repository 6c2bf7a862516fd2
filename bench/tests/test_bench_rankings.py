"""The cosine ranking module computes the numbers the harness computed
before rankings were named: the same reference answers, bit for bit, at
both precisions, and the same snapshot, key for key."""
import hashlib
import json

import ml_dtypes
import numpy as np
import pytest

from bench import corpus, deploy, run, traffic
from bench.tests import tiny

ROUND = {
    "float64": lambda x: np.asarray(x, np.float64),
    "bfloat16": lambda x: np.asarray(x, np.float64).astype(
        ml_dtypes.bfloat16).astype(np.float64),
}


def frozen_answer(c, terms, k, r):
    """The cosine formulas as the harness computed them: (ids, top,
    score)."""
    n = c.num_docs
    df = c.df()
    idf = r(np.where(df > 0, np.log1p(n / np.maximum(df, 1)), 0.0))
    doc = c.doc_of()
    w = r(c.tfs * idf[c.terms])
    norm = r(np.sqrt(np.bincount(doc, weights=w * w, minlength=n)))
    hit = [np.flatnonzero(c.terms == t) for t in terms]
    docs = np.concatenate([doc[h] for h in hit])
    ws = np.concatenate([r(c.tfs[h] * idf[t]) for h, t in zip(hit, terms)])
    acc = r(np.bincount(docs, weights=ws, minlength=n))
    qnorm = r(np.sqrt(np.sum(idf[terms] ** 2)))
    cand = np.flatnonzero(acc > 0)
    sc = r(acc[cand] / r(norm[cand] * qnorm))
    score = np.zeros(n)
    score[cand] = sc
    n_hit = min(k, len(cand))
    part = (np.argpartition(-sc, n_hit - 1)[:n_hit]
            if 0 < n_hit < len(cand) else np.arange(n_hit))
    best = cand[part[np.lexsort((cand[part], -sc[part]))]]
    return best, score[best], score


def _bits(a):
    a = np.ascontiguousarray(a)
    return a.dtype.str, a.shape, a.tobytes()


@pytest.mark.parametrize("precision", ["float64", "bfloat16"])
def test_cosine_reference_answers_as_before(precision):
    _, config, mix, _ = tiny.cell("paper-1m.table7")
    c = corpus.generate(config, tiny.SEED)
    qs = traffic.queries(mix, c.df(), c.token_counts(), c.num_docs, 24,
                         tiny.SEED)
    k = int(config["k"])
    ranking = run.load_ranking(config["ranking"])
    ref = ranking.Reference(c, {int(t) for q in qs for t in q},
                            precision=precision)
    for q in qs:
        got = ref.answer(q, k)
        want = frozen_answer(c, q, k, ROUND[precision])
        assert [_bits(x) for x in (got.ids, got.top, got.score)] == \
            [_bits(x) for x in want]


# per key of the snapshot the harness wrote for the tiny paper-1m corpus
# at tiny.SEED: sha256 of dtype, shape and bytes (meta without the
# layout choices the program's cost model makes)
SNAPSHOT = {
    "delta_lens": "991edaa5a86fe1f5",
    "delta_terms": "00b99a536446ebd1",
    "delta_tfs": "d158714a3a37a6bc",
    "df": "32eb7843599eab97",
    "hashes": "21ee1d63e754207e",
    "live": "bc899e3476688fb0",
    "meta": "77718198c7698054",
    "norm": "a78ac37b0576f6b3",
    "rank": "74355f0f30117e6c",
    "seg0_doc_of": "c5b59dbb8e5d79e0",
    "seg0_terms": "149d5b1d945791b9",
    "seg0_tfs": "65565afb99e8ad58",
    "seg1_doc_of": "81c0790abe826e12",
    "seg1_terms": "44005a4930b353ed",
    "seg1_tfs": "61218dbf697623f3",
    "seg2_doc_of": "27aa14784d030bf0",
    "seg2_terms": "1336dcaab207acea",
    "seg2_tfs": "513c56b66da6787b",
    "seg3_doc_of": "cd5e9eaf8f77f148",
    "seg3_terms": "fe2cad77c534289d",
    "seg3_tfs": "42c79dd2d7758616",
}


def digest(key, value):
    if key == "meta":
        meta = json.loads(np.asarray(value).tobytes())
        meta.pop("layout_policy")
        for s in meta["segments"]:
            for choice in ("layout", "size_class", "chooser_reason"):
                s.pop(choice)
        value = np.frombuffer(json.dumps(meta).encode(), np.uint8)
    a = np.ascontiguousarray(value)
    h = hashlib.sha256(f"{a.dtype.str} {a.shape}".encode())
    h.update(a.tobytes())
    return h.hexdigest()[:16]


def test_cosine_snapshot_is_as_before():
    _, config, _, _ = tiny.cell("paper-1m.table7")
    c = corpus.generate(config, tiny.SEED)
    state = deploy.snapshot_state(config, c, tiny.SEED,
                                  run.load_ranking(config["ranking"]))
    assert {k: digest(k, v) for k, v in state.items()} == SNAPSHOT
