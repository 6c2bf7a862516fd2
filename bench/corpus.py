"""The benchmark's own seeded corpus generator.

A document is a lognormal number of Zipf-distributed token draws over a
fixed vocabulary; its postings are the distinct terms drawn, each with
its count as tf.  The model is the one ``repro.text.corpus`` uses for
the paper's collection, kept here so that the yardstick does not move
when the program's generator does.  Everything is drawn in one
vectorized pass from ``seed``: no per-document or per-batch Python
loop.

Output is doc-major: ``doc_ptr[d]:doc_ptr[d + 1]`` slices ``terms`` and
``tfs`` for document ``d``, terms ascending within a document.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# keeps this generator's draws apart from the traffic generator's
_STREAM = 0x636F72


@dataclasses.dataclass(frozen=True)
class Corpus:
    doc_ptr: np.ndarray     # i64[N + 1]
    terms: np.ndarray       # i32[P]  term ids, ascending within a doc
    tfs: np.ndarray         # f32[P]  counts
    hashes: np.ndarray      # u32[V]  term id -> query hash (never 0)

    @property
    def num_docs(self) -> int:
        return len(self.doc_ptr) - 1

    @property
    def num_postings(self) -> int:
        return len(self.terms)

    @property
    def vocab(self) -> int:
        return len(self.hashes)

    def doc_of(self) -> np.ndarray:
        """i64[P]: the document of every posting."""
        return np.repeat(np.arange(self.num_docs, dtype=np.int64),
                         np.diff(self.doc_ptr))

    def df(self) -> np.ndarray:
        """i64[V] document frequency of every term."""
        return np.bincount(self.terms, minlength=self.vocab)

    def token_counts(self) -> np.ndarray:
        """f64[V] corpus frequency (tokens) of every term."""
        return np.bincount(self.terms, weights=self.tfs,
                           minlength=self.vocab)


def mix32(x: np.ndarray) -> np.ndarray:
    """Bijective 32-bit finalizer (murmur3's): 0 maps to 0 only."""
    x = np.asarray(x).astype(np.uint64)
    x ^= x >> np.uint64(16)
    x = (x * np.uint64(0x85EBCA6B)) & np.uint64(0xFFFFFFFF)
    x ^= x >> np.uint64(13)
    x = (x * np.uint64(0xC2B2AE35)) & np.uint64(0xFFFFFFFF)
    x ^= x >> np.uint64(16)
    return x.astype(np.uint32)


def term_hashes(vocab: int) -> np.ndarray:
    """Distinct non-zero hashes (0 is the server's empty query slot)."""
    return mix32(np.arange(1, vocab + 1, dtype=np.uint64))


def zipf_cdf(vocab: int, s: float) -> np.ndarray:
    p = np.arange(1, vocab + 1, dtype=np.float64) ** (-float(s))
    c = np.cumsum(p)
    return c / c[-1]


_GUIDE_BITS = 22
_CHUNK = 1 << 24


def zipf_ranks(u: np.ndarray, cdf: np.ndarray,
               guide: np.ndarray) -> np.ndarray:
    """i64 ranks of uniforms ``u``: the first rank whose cumulative
    probability reaches ``u`` (``np.searchsorted(cdf, u)``, clipped to
    the vocabulary).  A guide table of ``2**_GUIDE_BITS`` buckets gives
    each draw its bucket's first rank; the few draws past it step up."""
    tok = guide[(u * len(guide)).astype(np.int64)]
    last = len(cdf) - 1
    idx = np.flatnonzero(cdf[tok] < u)
    while idx.size:
        tok[idx] += 1
        idx = idx[(tok[idx] < last) & (cdf[tok[idx]] < u[idx])]
    return tok


def generate(spec: dict, seed: int) -> Corpus:
    """Draw the corpus of ``spec`` (keys ``num_docs``, ``vocab``,
    ``zipf_s``, ``doc_len_median``, ``doc_len_sigma``, ``doc_len_min``)
    from ``seed``."""
    n, vocab = int(spec["num_docs"]), int(spec["vocab"])
    rng = np.random.default_rng([_STREAM, int(seed)])
    raw = rng.lognormal(mean=np.log(float(spec["doc_len_median"])),
                        sigma=float(spec["doc_len_sigma"]), size=n)
    raw_len = np.clip(raw.astype(np.int64), int(spec["doc_len_min"]),
                      4 * vocab)
    key = np.repeat(np.arange(n, dtype=np.int64) * vocab, raw_len)
    cdf = zipf_cdf(vocab, spec["zipf_s"])
    guide = np.searchsorted(cdf, np.arange(1 << _GUIDE_BITS)
                            / (1 << _GUIDE_BITS)).astype(np.int64)
    for lo in range(0, len(key), _CHUNK):
        part = key[lo:lo + _CHUNK]
        part += zipf_ranks(rng.random(len(part)), cdf, guide)
    key.sort()
    first = np.empty(len(key), bool)
    first[0] = True
    np.not_equal(key[1:], key[:-1], out=first[1:])
    starts = np.flatnonzero(first)
    del first
    tfs = np.diff(np.append(starts, len(key))).astype(np.float32)
    uniq = key[starts]
    del key, starts
    doc = uniq // vocab
    terms = (uniq - doc * vocab).astype(np.int32)
    doc_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(doc, minlength=n), out=doc_ptr[1:])
    return Corpus(doc_ptr=doc_ptr, terms=terms, tfs=tfs,
                  hashes=term_hashes(vocab))
