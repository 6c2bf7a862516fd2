"""The BM25 pair walk: how to find it in a trace, and the bytes a batch
needs it to read beyond ``bench/walk.py``'s count.

The program names the walk specialised for BM25 ``pair_walk_bm25``
(``kernels/fused_decode_score.py``), and a TPU trace names each device
op by its HLO text, which starts with that name.

Under BM25 every posting's value depends on its document's length
normalisation ``K(d)``, a 4-byte row per document.  The walk reads it
per doc tile (``TILE`` documents): once for every tile a batch's routing
pairs visit in a segment.  A pair is (posting block, doc tile): a block
of 128 postings of one term visits every tile from its first
document's to its last's.  So the extra bytes of a batch are, per
segment, 4 * ``TILE`` times the number of distinct tiles that the
blocks of the batch's terms span.
"""
from __future__ import annotations

import numpy as np

PATTERN = r"pair_walk_bm25"
BLOCK = 128
TILE = 512          # the walk's doc tile (the program's default width)
ROW_BYTES = 4       # one f32 K(d) a document


class LengthRowBytes:
    """Per (segment, term) doc tiles, for the terms asked about."""

    def __init__(self, segments, terms):
        """``segments``: [(local doc ids, term ids)] of each served
        segment's postings; ``terms``: every term id a batch may hold."""
        self._tiles: dict = {}
        want = np.asarray(sorted(set(int(t) for t in terms)), np.int64)
        for si, (docs, tids) in enumerate(segments):
            tids = np.asarray(tids, np.int64)
            keep = np.isin(tids, want)
            t, d = tids[keep], np.asarray(docs, np.int64)[keep]
            order = np.lexsort((d, t))
            t, d = t[order], d[order]
            bounds = np.flatnonzero(np.diff(t)) + 1
            for tt, dd in zip(np.split(t, bounds), np.split(d, bounds)):
                if not len(tt):
                    continue
                first = dd[::BLOCK] // TILE
                last = dd[np.minimum(np.arange(len(first)) * BLOCK
                                     + BLOCK - 1, len(dd) - 1)] // TILE
                self._tiles[(si, int(tt[0]))] = np.unique(np.concatenate(
                    [np.arange(a, b + 1) for a, b in zip(first, last)]))
        self.n_segments = len(segments)

    @classmethod
    def from_index(cls, index, terms):
        """From a served live index's sealed segments (their stored
        postings, the corpus's own doc and term ids)."""
        return cls([(s.doc_of, s.terms) for s in index.view().segments],
                   terms)

    def batch(self, queries) -> int:
        """K-row bytes one scored batch needs: ``queries`` are its
        term-id arrays."""
        terms = {int(t) for q in queries for t in q}
        total = 0
        for si in range(self.n_segments):
            spans = [self._tiles[(si, t)] for t in terms
                     if (si, t) in self._tiles]
            if spans:
                total += len(np.unique(np.concatenate(spans)))
        return total * TILE * ROW_BYTES
