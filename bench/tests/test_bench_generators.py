"""The seeded generators: deterministic, and true to their specs."""
import numpy as np
import pytest

from bench import corpus, traffic

SPEC = dict(num_docs=4000, vocab=3000, zipf_s=1.07, doc_len_median=64,
            doc_len_sigma=0.5, doc_len_min=4)
BIG_SEED = 2**31 + 12345


def test_corpus_is_a_function_of_the_seed():
    a, b = corpus.generate(SPEC, BIG_SEED), corpus.generate(SPEC, BIG_SEED)
    c = corpus.generate(SPEC, BIG_SEED + 1)
    assert np.array_equal(a.terms, b.terms) and np.array_equal(a.tfs, b.tfs)
    assert not np.array_equal(a.terms[:1000], c.terms[:1000])


def test_corpus_matches_its_spec():
    c = corpus.generate(SPEC, 3)
    lens = np.diff(c.doc_ptr)
    # token draws per doc: lognormal around the median, counts sum to it
    tokens = np.add.reduceat(c.tfs, c.doc_ptr[:-1].astype(np.int64))
    assert abs(np.median(tokens) - SPEC["doc_len_median"]) < 3
    assert tokens.min() >= SPEC["doc_len_min"]
    # distinct and ascending terms within a doc
    d = c.doc_of()
    same = d[1:] == d[:-1]
    assert np.all(np.diff(c.terms)[same] > 0)
    assert lens.sum() == c.num_postings
    # Zipf: token counts fall with rank at about the exponent
    counts = c.token_counts()
    ranks = np.arange(1, 101)
    slope = np.polyfit(np.log(ranks), np.log(counts[:100]), 1)[0]
    assert -1.2 < slope < -0.95
    # hashes are distinct and never the empty slot
    assert len(np.unique(c.hashes)) == c.vocab and c.hashes.min() > 0


@pytest.mark.parametrize("rate,seconds", [(7.0, 3.0), (40.0, 2.5)])
def test_arrivals_fill_the_window_alike_for_every_seed(rate, seconds):
    mix = {"rate_qps": rate}
    a = traffic.arrivals(mix, seconds)
    assert len(a) == round(rate * seconds)
    assert np.all(np.diff(a) >= 0) and a[0] == 0 and a[-1] < seconds
    # exponential gaps, shuffled: neither sorted nor evenly spaced
    gaps = np.diff(a)
    assert not np.all(np.diff(gaps) >= 0)
    assert np.std(gaps) > 0.5 * np.mean(gaps)
    # the schedule is not drawn from the seed: only the queries are
    other = traffic.arrivals(dict(mix), seconds)
    assert np.array_equal(a, other)


DEV_LIKE = {"lengths": {"1": 0.10, "2": 0.18, "3": 0.25, "4": 0.21,
                        "5": 0.13, "6": 0.07, "7": 0.04, "8": 0.02},
            "terms": {"rule": "corpus_frequency", "skip_top": 33}}


def test_lengths_keep_the_shares():
    mix = DEV_LIKE
    lens = traffic.lengths(mix, 1000, BIG_SEED)
    for k, share in mix["lengths"].items():
        assert abs(np.mean(lens == int(k)) - share) <= 0.001
    assert abs(lens.mean() - 3.56) < 0.01
    assert np.array_equal(np.sort(lens),
                          np.sort(traffic.lengths(mix, 1000, 5)))


def test_table7_terms_come_from_the_df_band():
    c = corpus.generate(dict(SPEC, vocab=700), 4)
    df = c.df()
    mix = traffic.load("table7")
    qs = traffic.queries(mix, df, c.token_counts(), c.num_docs, 200, 8)
    frac = df / c.num_docs
    for q in qs:
        assert 1 <= len(q) <= 4 and len(set(q.tolist())) == len(q)
        assert np.all((frac[q] >= 0.15) & (frac[q] <= 0.5))
    again = traffic.queries(mix, df, c.token_counts(), c.num_docs, 200, 8)
    assert all(np.array_equal(x, y) for x, y in zip(qs, again))


def test_dev_terms_follow_frequency_without_the_top():
    c = corpus.generate(dict(SPEC, vocab=20000, zipf_s=1.0), 4)
    tok = c.token_counts()
    mix = DEV_LIKE
    qs = traffic.queries(mix, c.df(), tok, c.num_docs, 600, 8)
    top = set(np.argsort(-tok, kind="stable")[:33].tolist())
    drawn = np.concatenate(qs)
    assert not top.intersection(drawn.tolist())
    assert all(len(set(q.tolist())) == len(q) for q in qs)
    # drawn in proportion to frequency: frequent terms come more often
    ranks = np.argsort(np.argsort(-tok, kind="stable"))
    assert np.median(ranks[drawn]) < 0.2 * np.count_nonzero(tok)


@pytest.mark.parametrize("vocab,s", [(216449, 1.07), (700, 1.07), (3, 0.5)])
def test_zipf_ranks_are_the_inverse_cdf(vocab, s):
    cdf = corpus.zipf_cdf(vocab, s)
    guide = np.searchsorted(cdf, np.arange(1 << 16) / (1 << 16))
    u = np.random.default_rng(BIG_SEED).random(200_000)
    u[:3] = [0.0, np.nextafter(1.0, 0.0), cdf[0]]
    want = np.minimum(np.searchsorted(cdf, u), vocab - 1)
    assert np.array_equal(corpus.zipf_ranks(u, cdf, guide), want)
