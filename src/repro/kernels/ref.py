"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``ref_*`` function is the semantic ground truth; kernel tests sweep
shapes/dtypes and ``assert_allclose`` kernel-vs-oracle.  The oracles are
also the XLA fallback path used on hardware without Pallas support.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


# ---------------------------------------------------------------------------
# posting_score: blocked posting-list scoring (the q_occ + accumulate phase)
# ---------------------------------------------------------------------------


def ref_posting_score(block_docs: Array, block_tfs: Array, block_w: Array,
                      num_docs: int) -> Array:
    """Scatter-add tf*w of every valid posting into a dense score vector.

    block_docs i32[NB, B] (-1 = padding), block_tfs f32[NB, B],
    block_w f32[NB] per-block term weight (idf * query weight).
    """
    docs = block_docs.reshape(-1)
    w = (block_tfs * block_w[:, None]).reshape(-1)
    valid = docs >= 0
    tgt = jnp.where(valid, docs, num_docs)
    acc = jnp.zeros((num_docs + 1,), jnp.float32)
    acc = acc.at[tgt].add(jnp.where(valid, w, 0.0), mode="drop")
    return acc[:num_docs]


# ---------------------------------------------------------------------------
# packed_postings: delta + bit-packed doc-id block decode
# ---------------------------------------------------------------------------


def ref_unpack_block(packed: Array, bits: Array, base: Array, count: Array,
                     block: int) -> Array:
    """Decode one packed block -> doc ids i32[block] (-1 past count).

    packed u32[words], bits/base/count scalars.
    """
    lane = jnp.arange(block, dtype=jnp.uint32)
    bitpos = lane * bits.astype(jnp.uint32)
    wi = (bitpos >> 5).astype(jnp.int32)
    off = bitpos & jnp.uint32(31)
    lo = packed[wi] >> off
    hi = jnp.where(off > 0,
                   packed[jnp.minimum(wi + 1, packed.shape[0] - 1)]
                   << (jnp.uint32(32) - off), jnp.uint32(0))
    raw = lo | hi
    mask = jnp.where(bits >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << bits.astype(jnp.uint32)) - 1)
    deltas = (raw & mask).astype(jnp.int32)
    docs = base + jnp.cumsum(deltas, dtype=jnp.int32)
    return jnp.where(jnp.arange(block) < count, docs, -1)


def ref_unpack_blocks(packed: Array, bits: Array, base: Array, count: Array,
                      block: int) -> Array:
    return jax.vmap(lambda p, b, ba, c: ref_unpack_block(p, b, ba, c, block)
                    )(packed, bits, base, count)


# ---------------------------------------------------------------------------
# embedding_bag: fixed multi-hot bag sum (recsys hot path)
# ---------------------------------------------------------------------------


def ref_embedding_bag(table: Array, indices: Array,
                      mode: str = "sum") -> Array:
    """table f32[V, D], indices i32[B, H] (-1 = padding) -> f32[B, D]."""
    safe = jnp.maximum(indices, 0)
    rows = table[safe]                               # [B, H, D]
    valid = (indices >= 0)[..., None].astype(table.dtype)
    rows = rows * valid
    if mode == "sum":
        return rows.sum(axis=1)
    if mode == "mean":
        n = jnp.maximum(valid.sum(axis=1), 1.0)
        return rows.sum(axis=1) / n
    raise ValueError(mode)


# ---------------------------------------------------------------------------
# segment_multi_agg: PNA fused mean/min/max/std over padded neighbor lists
# ---------------------------------------------------------------------------


def ref_pna_multi_agg(feats: Array, nbr: Array, eps: float = 1e-5) -> Array:
    """feats f32[Nsrc, D], nbr i32[N, K] (-1 pad) -> f32[N, 4D].

    Output channels: [mean | min | max | std] (PNA's four aggregators,
    fused so the neighbor features are read ONCE).
    """
    safe = jnp.maximum(nbr, 0)
    x = feats[safe]                                  # [N, K, D]
    valid = (nbr >= 0)[..., None]
    n = jnp.maximum(valid.sum(axis=1).astype(feats.dtype), 1.0)
    xs = jnp.where(valid, x, 0.0)
    mean = xs.sum(axis=1) / n
    mn = jnp.where(valid, x, jnp.inf).min(axis=1)
    mn = jnp.where(jnp.isfinite(mn), mn, 0.0)
    mx = jnp.where(valid, x, -jnp.inf).max(axis=1)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    mean_sq = jnp.where(valid, x * x, 0.0).sum(axis=1) / n
    std = jnp.sqrt(jnp.maximum(mean_sq - mean * mean, 0.0) + eps)
    return jnp.concatenate([mean, mn, mx, std], axis=-1)


# ---------------------------------------------------------------------------
# flash_attention: causal / sliding-window attention with GQA
# ---------------------------------------------------------------------------


def ref_attention(q: Array, k: Array, v: Array, causal: bool = True,
                  window: int = 0, scale: float | None = None) -> Array:
    """q f32[B, Hq, S, Dh], k/v f32[B, Hkv, S, Dh] -> f32[B, Hq, S, Dh].

    GQA: Hq must be a multiple of Hkv.  ``window`` > 0 limits attention to
    the last ``window`` positions (sliding-window / Mistral-style).
    """
    b, hq, s, dh = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    if scale is None:
        scale = dh ** -0.5
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= kpos <= qpos
    if window and window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv)


# ---------------------------------------------------------------------------
# BM25: the plain reference of the ranking the live index serves
# ---------------------------------------------------------------------------


def ref_bm25_topk(doc_term_ids, doc_counts, queries, k: int, k1: float,
                  b: float):
    """Exact BM25 top-k over a plain corpus, with no kernel, batching or
    segments: the reference the served path is compared with.

    ``doc_term_ids`` / ``doc_counts``: one array of distinct term ids
    and one of their counts per document (ids are the list positions).
    ``queries``: term-id arrays (distinct; ids absent from the corpus
    score nothing).  For each query term ``t`` and document ``d``, in
    float32 numpy (no fused multiply-add, no matmul)::

        idf(t)   = ln(1 + (N - df(t) + 0.5) / (df(t) + 0.5))
        K(d)     = k1 (1 - b + b dl(d) / avgdl)
        score(d) = sum_t idf(t) tf (k1 + 1) / (tf + K(d))

    with ``N`` the document count, ``dl(d)`` the document's summed
    counts and ``avgdl`` their mean.  A document's terms are added in
    ascending term id, and the best ``k`` come first, ties to the lower
    id.  Returns (ids i32[Q, k], scores f32[Q, k]), -1 / 0 past the
    hits."""
    import numpy as np

    f32 = np.float32
    n = len(doc_term_ids)
    lens = np.array([len(t) for t in doc_term_ids], np.int64)
    terms = (np.concatenate(doc_term_ids).astype(np.int64) if n
             else np.zeros(0, np.int64))
    tfs = (np.concatenate(doc_counts).astype(f32) if n
           else np.zeros(0, f32))
    docs = np.repeat(np.arange(n), lens)
    df = np.bincount(terms, minlength=int(terms.max(initial=-1)) + 1)
    dl = np.array([int(np.sum(c)) for c in doc_counts], np.int64)
    avgdl = f32(dl.sum() / n if n else 1.0)
    kd = f32(k1) * (f32(1.0 - b) + f32(b) * (dl.astype(f32) / avgdl))
    out_ids = np.full((len(queries), k), -1, np.int32)
    out_scores = np.zeros((len(queries), k), f32)
    for i, q in enumerate(queries):
        acc = np.zeros(n, f32)
        for t in sorted(int(x) for x in q if 0 <= x < len(df)):
            if df[t] == 0:
                continue
            idf = np.log1p((f32(n) - f32(df[t]) + f32(0.5))
                           / (f32(df[t]) + f32(0.5)))
            hit = terms == t
            tf, d = tfs[hit], docs[hit]
            acc[d] = acc[d] + tf * f32(k1 + 1.0) / (tf + kd[d]) * idf
        cand = np.flatnonzero(acc > 0)
        best = cand[np.lexsort((cand, -acc[cand]))][:k]
        out_ids[i, :len(best)] = best
        out_scores[i, :len(best)] = acc[best]
    return out_ids, out_scores
