"""The pair-walk kernel: how to find it in a trace, and the bytes a
batch needs it to read.

The walk is the one ``pallas_call`` of ``kernels/fused_decode_score.py``
on the served path; a TPU trace names each device op by its HLO text,
and the walk's is the only ``tpu_custom_call`` (the routing, the delta
scan and the doc-table work are XLA ops).

Bytes are counted from the benchmark's own corpus, as the work the
query needs, not as what a given kernel moves: for each segment and
each distinct term of the batch, the posting blocks (128 postings) of
that term in that segment, at the segment's layout, plus the k best
(value, id) pairs per query and segment written back.

* ``hor``: a block stores 128 i32 doc ids and 128 f32 tfs, 1,024 bytes.
* ``packed``: a block stores 128 f16 tfs and its doc-id gaps bit-packed
  at the block's own width (the widest gap in it), in 32-bit words.

Any other layout has no count here, and the metrics that need one are
left out of the run's line.
"""
from __future__ import annotations

import numpy as np

PATTERN = r'custom_call_target="tpu_custom_call"'
BLOCK = 128
HOR_BLOCK_BYTES = BLOCK * (4 + 4)


def _packed_term_bytes(docs: np.ndarray) -> int:
    n_blocks = -(-len(docs) // BLOCK)
    rows = np.pad(docs, (0, n_blocks * BLOCK - len(docs)), mode="edge")
    widest = np.maximum(np.diff(rows.reshape(n_blocks, BLOCK), axis=1)
                        .max(axis=1), 1)
    bits = np.floor(np.log2(widest)).astype(np.int64) + 1
    words = -(-BLOCK * bits // 32)
    return int(np.sum(4 * words + 2 * BLOCK))


class WalkBytes:
    """Per (segment, term) posting bytes, for the terms asked about."""

    def __init__(self, corpus, segments, terms):
        """``segments``: [(doc_base, doc_span, layout)] of the served
        stack; ``terms``: every term id a batch may hold."""
        self.ok = all(lay in ("hor", "packed") for _, _, lay in segments)
        self.segments = segments
        self._bytes: dict = {}
        if not self.ok:
            return
        want = np.zeros(corpus.vocab, bool)
        want[np.asarray(sorted(set(terms)), np.int64)] = True
        doc = corpus.doc_of()
        keep = want[corpus.terms]
        t_all, d_all = corpus.terms[keep], doc[keep]
        order = np.lexsort((d_all, t_all))
        t_all, d_all = t_all[order], d_all[order]
        for si, (base, span, layout) in enumerate(segments):
            m = (d_all >= base) & (d_all < base + span)
            t, d = t_all[m], d_all[m]
            bounds = np.flatnonzero(np.diff(t)) + 1
            for tt, dd in zip(np.split(t, bounds), np.split(d, bounds)):
                if not len(tt):
                    continue
                if layout == "hor":
                    b = -(-len(dd) // BLOCK) * HOR_BLOCK_BYTES
                else:
                    b = _packed_term_bytes(dd)
                self._bytes[(si, int(tt[0]))] = b

    def batch(self, queries, k: int) -> int | None:
        """Bytes one scored batch needs: ``queries`` are its term-id
        arrays (cache hits excluded)."""
        if not self.ok:
            return None
        terms = set()
        for q in queries:
            terms.update(int(t) for t in q)
        posting = sum(self._bytes.get((si, t), 0)
                      for si in range(len(self.segments)) for t in terms)
        return posting + len(self.segments) * len(queries) * k * 8
