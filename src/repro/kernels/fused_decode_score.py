"""Pallas TPU kernel: fused batched decode-and-score — one HBM pass from
(possibly bit-packed) posting blocks to dense per-query scores, or (the
candidate path) straight to per-tile top-k candidates.

The paper's §4.3 claim is that query cost is dominated by posting-list
I/O, so the compressed layout must NOT be decompressed through HBM
before scoring.  One kernel invocation walks the tile-sorted routing
pairs ``(block, tile)`` and, per pair,

  1. DMAs ONE posting row into VMEM — either raw int32 doc ids
     (HOR/BlockedIndex) or delta+bit-packed u32 words (PackedCsrIndex);
  2. for packed blocks, unpacks IN VMEM (per-lane variable shifts over
     a lane gather of the word row + a log-step lane prefix sum);
  3. scatters ``qw * tf`` (under BM25 ``qw`` times the saturated tf,
     with the document's length row read off the tile) into a
     ``tile``-wide doc tile with a one-hot matmul on the MXU and adds
     it to a ``[Q, tile]`` accumulator — a hot block is read ONCE and
     serves every query in the batch that touches it.

Routing pairs are deduplicated across the query batch (two queries
sharing a term share the block read) and sorted by tile so each output
tile stays resident in VMEM for one contiguous run of pairs.  The
block -> tile span table is a build-time cache on the index
(``tile_first``/``tile_count``), not a per-query computation.  The
kernels DMA the rows the layouts store: 128-lane word rows and u32
tf-pair rows for packed blocks (Mosaic DMAs neither a single 16-bit row
nor a row narrower than 128 lanes, and loads no f16 on v5e), so no call
copies the index.

CANDIDATE EXTRACTION (the ``fused_topk_*`` variants): the dense engine
still wrote a ``[Q, num_docs]`` score array to HBM before ``top_k`` —
at corpus scale that write dwarfs the compressed posting bytes the read
path saved.  The candidate kernels keep the ``[Q, tile]`` accumulator in
VMEM scratch; when the walk leaves a tile (tile-sorted pairs make its
run contiguous) the accumulator is reduced IN VMEM to a per-tile
candidate set:

  * the ranking's doc-metadata tail (``Ranking.tail``: deleted-doc
    mask, cosine's norm division, static-rank blend — bit-identical op
    sequence to the jnp oracle's scoring tail) is applied to the
    resident tile, and
  * ``k_tile`` successive maxima are extracted (lowest-lane tie-break,
    matching ``jax.lax.top_k``) as (value, global doc id) pairs.

Only ``O(Q * n_tiles * k_tile)`` candidates ever reach HBM; a pure
``merge_topk_candidates`` (distributed/topk.py) over the tile-major
candidate lists reproduces the dense oracle's ranked ids bit-exactly
because per-tile lists are value-sorted with ascending-id ties and tiles
are concatenated in ascending doc order.  ``k_tile >= min(k, tile)``
guarantees no global top-k entry is lost.

HBM bytes per batch ~ sum over unique (block, tile) pairs of the block's
payload: ``4*ceil(128*bits/32) + 2*128`` bytes packed vs ``8*128`` bytes
unpacked (the layouts' accounting; the DMAs move whole rows, ``4*128``
bytes of words plus a ``4*128``-byte tf-pair row per packed pair), plus
``Q * n_tiles * k_tile * 8`` candidate bytes out (vs ``Q * num_docs *
4`` dense) — the roofline benchmark reports both ratios.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ranking import TFIDF_COSINE, Ranking
from repro.kernels.runtime import resolve_interpret

Array = jax.Array

TILE = 512   # doc-space tile width (4 x 128 lanes), matches posting_score
Q_PAD = 8    # query-batch padding quantum (f32 sublane width)
K_PAD = 8    # candidate-count padding quantum (per-tile k_tile lanes)


def default_k_tile(k: int, tile: int = TILE, k_pad: int = K_PAD) -> int:
    """Per-tile candidate count: >= min(k, tile) (exactness floor),
    rounded up to the ``k_pad`` lane quantum, never wider than the tile.

    The ``min(tile, ...)`` clamp is load-bearing for autotuned tile
    widths: a narrow tile (e.g. 256) cannot emit more than ``tile``
    candidates, and every kernel entry point rejects ``k_tile > tile``
    rather than silently truncating (see ``_check_k_tile``)."""
    k_pad = max(int(k_pad), 1)
    return min(tile, max(k_pad, -(-max(k, 1) // k_pad) * k_pad))


def _check_k_tile(k_tile: int, tile: int) -> None:
    """Reject geometry the per-tile reduction cannot satisfy.  Call
    sites that assumed ``TILE = 512`` must clamp via ``default_k_tile(k,
    tile)`` (which never exceeds the tile) before reaching a kernel."""
    if k_tile > tile:
        raise ValueError(
            f"k_tile={k_tile} > tile={tile}: a {tile}-wide doc tile "
            f"cannot emit {k_tile} candidates — clamp with "
            "default_k_tile(k, tile)")
    if k_tile < 1:
        raise ValueError(f"k_tile must be >= 1, got {k_tile}")


def _scan_lanes(x):
    """Inclusive prefix sum along the lanes of an int32 ``[1, n]`` row.

    Mosaic has no ``cumsum``; a log-step scan of ``pltpu.roll`` + lane
    masks does the same integer additions in a different association,
    which integer arithmetic makes bit-identical to ``jnp.cumsum``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    s = 1
    while s < x.shape[1]:
        x = x + jnp.where(lane >= s, pltpu.roll(x, s, 1), 0)
        s *= 2
    return x


def _take_lanes(x, idx):
    """``x[0, idx[0, l]]`` for every lane ``l`` of two int32 ``[1, n]``
    rows.  Mosaic lowers only vreg-shaped 2-D lane gathers, so both rows
    are broadcast to 8 sublanes and one is kept."""
    shape = (8, x.shape[1])
    return jnp.take_along_axis(
        jnp.broadcast_to(x, shape), jnp.broadcast_to(idx, shape), axis=1,
        mode="promise_in_bounds")[:1]


def _unpack_block_row(words, bits, base, count, block: int):
    """In-VMEM decode of one delta+bit-packed block.

    ``words`` is the block's u32 word row, lane-padded to a multiple of
    128 (``>= block``); ``bits``/``base``/``count`` are the block's
    decode scalars.  Returns the i32 ``[1, block]`` doc-id row (-1 past
    ``count``)."""
    width = words.shape[1]
    w = jax.lax.bitcast_convert_type(words, jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    bitpos = lane * bits
    wi = jnp.minimum(bitpos >> 5, width - 1)
    off = (bitpos & 31).astype(jnp.uint32)
    lo = jax.lax.bitcast_convert_type(_take_lanes(w, wi), jnp.uint32) >> off
    nxt = jax.lax.bitcast_convert_type(
        _take_lanes(w, jnp.minimum(wi + 1, width - 1)), jnp.uint32)
    hi = jnp.where(off > 0, nxt << (jnp.uint32(32) - off), jnp.uint32(0))
    mask = jnp.where(bits >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << bits.astype(jnp.uint32))
                     - jnp.uint32(1))
    deltas = jax.lax.bitcast_convert_type((lo | hi) & mask, jnp.int32)
    docs = base + _scan_lanes(deltas)
    return jnp.where(lane < count, docs, -1)[:, :block]


def _f16_bits_to_f32(h):
    """Exact f16 -> f32 of the 16-bit patterns held in int32 ``h``,
    with integer ops and bitcasts only (Mosaic loads no f16 on v5e):
    normals rebias the exponent, subnormals are ``m * 2**-24``, and
    inf/nan keep their mantissa."""
    sign = (h >> 15) << 31
    e = (h >> 10) & 31
    m = h & 1023
    normal = jax.lax.bitcast_convert_type(
        sign | ((e + 112) << 23) | (m << 13), jnp.float32)
    special = jax.lax.bitcast_convert_type(
        sign | (255 << 23) | (m << 13), jnp.float32)
    sub = m.astype(jnp.float32) * jnp.float32(2.0 ** -24)
    sub = jnp.where(sign != 0, -sub, sub)
    return jnp.where(e == 0, sub, jnp.where(e == 31, special, normal))


def _unpair_tf_row(pairs, half):
    """f32 tfs of one packed block from its u32 tf-pair row
    (``layouts.pair_tf_rows``): the f16 bits in half ``half`` (0 low,
    1 high) of every lane."""
    w = jax.lax.bitcast_convert_type(pairs, jnp.int32)
    return _f16_bits_to_f32((w >> (16 * half)) & 0xFFFF)


def _tile_contribution(docs, tfs, qw, tile_base, lane_cap, tile: int,
                       ranking: Ranking = TFIDF_COSINE, row=None):
    """Shared scoring step: ``[Q, tile]`` contribution of one block.

    ``docs``/``tfs`` are ``[1, B]`` rows, ``qw`` the ``[Q, 1]`` query
    weight column.  Each posting's weighted value is one f32 number (as
    in the oracle): ``qw * tf``, or under a saturating ranking (BM25)
    ``qw * ranking.posting(tf, K)`` with the posting's doc row ``K``
    read off the tile's row ``row`` ``[1, tile]`` by the transposed
    one-hot product.  The one-hot ``[Q, B] x [tile, B]^T`` MXU product
    then only places the values: postings of one block are distinct
    docs, so every output lane sums one value and zeros, exactly, at
    HIGHEST precision, and the accumulator adds values already rounded
    (no multiply for a backend to fuse into the add).  ``lane_cap``
    truncates the block at posting granularity so a per-term ``cap``
    that cuts mid-block matches the oracle's gather."""
    block = docs.shape[1]
    lane0 = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    local = docs - tile_base
    inb = (docs >= 0) & (local >= 0) & (local < tile) & (lane0 < lane_cap)
    slot = jax.lax.broadcasted_iota(jnp.int32, (tile, block), 0)
    onehot = (slot == local).astype(jnp.float32)                 # [tile, B]
    tf = jnp.where(inb, tfs, 0.0)                                # [1, B]
    if ranking.saturates:
        k_post = jax.lax.dot_general(
            jnp.broadcast_to(row, (qw.shape[0], tile)), onehot,
            (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)                  # [Q, B]
        w = qw * ranking.posting(tf, k_post)                     # [Q, B]
    else:
        w = qw * tf                                              # [Q, B]
    return jax.lax.dot_general(
        w, onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                      # [Q, tile]


def _final_from_acc(acc, row, rank, qscale, rank_blend: float,
                    ranking: Ranking):
    """The ranking's q_doc scoring tail, applied to one resident tile
    (``row``/``rank`` ``[1, tile]`` rows, ``qscale`` a ``[Q, 1]``
    column).

    Delegates to the ONE shared definition (``Ranking.tail``) so
    candidate values stay bit-identical to the dense reference — any
    change to the tail changes both sides at once.
    """
    return ranking.tail(acc, row, rank, qscale, rank_blend)


def _tile_topk(final, base, k_tile: int, tile: int):
    """Extract k_tile successive maxima from a [Q, tile] tile in VMEM.

    Tie-break: lowest lane (== lowest doc id) first — the same order
    ``jax.lax.top_k`` produces, so the host-side merge of per-tile lists
    matches a dense top_k exactly.  Exhausted rows yield (-inf, -1).
    """
    q = final.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, tile), 1)
    kidx = jax.lax.broadcasted_iota(jnp.int32, (q, k_tile), 1)

    def body(j, carry):
        work, vals, ids = carry
        m = jnp.max(work, axis=1, keepdims=True)                # [Q, 1]
        am = jnp.min(jnp.where(work == m, lane, tile), axis=1,
                     keepdims=True)
        gid = jnp.where(m > -jnp.inf, base + am, -1)
        sel = kidx == j
        vals = jnp.where(sel, m, vals)
        ids = jnp.where(sel, gid, ids)
        work = jnp.where(lane == am, -jnp.inf, work)
        return work, vals, ids

    _, vals, ids = jax.lax.fori_loop(
        0, k_tile, body,
        (final, jnp.full((q, k_tile), -jnp.inf, jnp.float32),
         jnp.full((q, k_tile), -1, jnp.int32)))
    return vals, ids


def _swap_stride(x, j: int):
    """Exchange each lane with its partner ``lane ^ j`` along the last
    axis (j a power of two dividing the width).  Implemented as a
    reshape + reversal of the pair axis — lane i decomposes as
    ``g*(2j) + h*j + r`` with ``h`` the bit ``i & j``; flipping ``h``
    is exactly the xor.  NOTE: Mosaic restricts reshapes that move the
    minor (lane) dimension; this helper keeps the minor dim intact
    (``r < j`` stays minor) except at j == 1, which only interpret mode
    handles — ``_check_reducer`` refuses the bitonic reducer on compiled
    lowerings until a roll-based j == 1 exchange replaces this stage.
    """
    q, n = x.shape
    y = x.reshape(q, n // (2 * j), 2, j)
    return y[:, :, ::-1, :].reshape(q, n)


def _tile_topk_bitonic(final, base, k_tile: int, tile: int):
    """Bitonic partial-sort tile reducer: full (value desc, lane asc)
    bitonic sort of the [Q, tile] tile, then the first ``k_tile``
    columns ARE the per-tile candidates.

    Bit-identical to ``_tile_topk``'s successive maxima by construction:
    both orders are the same strict total order (value descending,
    lowest lane wins ties — lanes are distinct, so the order is total
    and the sort is trivially stable), and the sort only PERMUTES the
    score values, never recomputes them, so candidate floats match to
    the bit.  Non-finite survivors map to id -1 exactly as in
    ``_tile_topk``.  Cost is the fixed ``log2(tile)*(log2(tile)+1)/2``
    compare-exchange stages (45 for tile=512) against ``k_tile``
    max+argmin passes — the autotuner decides per shape which wins.
    """
    if tile & (tile - 1):
        raise ValueError(f"bitonic reducer needs a power-of-two tile, "
                         f"got {tile}")
    q = final.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, tile), 1)
    v, l = final, lane
    size = 2
    while size <= tile:
        stride = size // 2
        while stride >= 1:
            pv = _swap_stride(v, stride)
            pl_ = _swap_stride(l, stride)
            lo = (lane & stride) == 0         # low element of its pair
            desc = (lane & size) == 0         # block direction this stage
            # self precedes partner in (value desc, lane asc) order
            first = (v > pv) | ((v == pv) & (l < pl_))
            keep = jnp.where(lo == desc, first, ~first)
            v = jnp.where(keep, v, pv)
            l = jnp.where(keep, l, pl_)
            stride //= 2
        size *= 2
    vals = v[:, :k_tile]
    ids = jnp.where(jnp.isfinite(vals), base + l[:, :k_tile], -1)
    return vals, ids


REDUCERS = ("successive", "bitonic")


def _tile_reduce(final, base, k_tile: int, tile: int, reducer: str):
    """Reducer dispatch shared by the candidate kernels.  Both branches
    are pure jnp, so this same function IS the reference mirror — tests
    call it outside any kernel to compare reducers bit-for-bit."""
    if reducer == "bitonic":
        return _tile_topk_bitonic(final, base, k_tile, tile)
    if reducer == "successive":
        return _tile_topk(final, base, k_tile, tile)
    raise ValueError(f"unknown reducer {reducer!r}; expected {REDUCERS}")


# ---------------------------------------------------------------------------
# the pair walk: one kernel body behind all four entry points
# ---------------------------------------------------------------------------
#
# A routing budget may be the whole-index bound (``scaled_pairs_budget``,
# 2**27 pairs at a million-doc class) while SMEM holds 1 MiB.  So the
# per-pair scalars stay in HBM and are staged into SMEM ``CHUNK`` pairs
# at a time; posting rows are DMA'd by the staged block index into VMEM
# (a one-row BlockSpec breaks Mosaic's (8, 128) block rule).  One
# invocation walks the tile-sorted pairs in order: a tile change flushes
# the previous tile (dense: the accumulator; candidates: scoring tail +
# per-tile top-k) to its HBM row, so the op sequence per doc is the one
# the interpreter always ran.  Only pairs up to the last real one are
# walked; tiles never flushed stay garbage and are masked by
# ``_finish*`` from ``pair_tile``.

CHUNK = 1024   # pairs staged per SMEM fill (the 1-D HBM tiling quantum)
LANES = 128


def _lane_pad(n: int) -> int:
    return -(-int(n) // LANES) * LANES


def _n_walk(pair_tile: Array, pair_cap: Array, n_tiles: int) -> Array:
    """Pairs to walk: through the last real pair.  Unused budget slots
    are trash-tile pairs sorted to the end (and a pair with cap 0 reads
    no lane); neither can change a result."""
    idx = jnp.arange(pair_tile.shape[0], dtype=jnp.int32) + 1
    real = (pair_tile < n_tiles) & (pair_cap > 0)
    return jnp.max(jnp.where(real, idx, 0), initial=0).reshape(1)


def _pad_pairs(x: Array, n: int) -> Array:
    return jnp.pad(x.astype(jnp.int32), (0, n - x.shape[0]))


def _walk_pairs(rows: Array, tfs: Array, pair_block: Array,
                pair_tile: Array, pair_qw: Array, pair_cap: Array,
                decode, num_docs: int, tile: int, block: int,
                interpret: bool, tail=None):
    """Run the pair walk; returns the raw per-tile HBM output(s).

    ``rows`` are the posting rows the kernel DMAs per pair: i32 doc ids
    (HOR) or 128-lane u32 word rows (packed, with ``decode`` the
    per-pair (bits, base, count)).  ``tfs`` is f32[NB, block] (HOR) or
    the u32[ceil(NB/2), block] tf-pair rows (packed).  ``tail`` is None for
    the dense engine (output f32[n_tiles+1, Q, tile]) or
    ``(row_t, rank_t, qscale, k_tile, rank_blend, reducer, ranking)``
    for the candidate engine (outputs f32/i32[n_tiles+1, Q,
    lane_pad(k_tile)]).  A saturating ranking (BM25) DMAs a tile's doc
    row when the walk enters the tile, since every pair of the tile
    needs it; cosine reads it only at the flush.  The kernel is named
    ``pair_walk`` under cosine and ``pair_walk_<ranking>`` otherwise,
    so a trace tells them apart."""
    np_pairs, q = pair_qw.shape
    n_tiles = max(-(-num_docs // tile), 1)
    npc = max(-(-np_pairs // CHUNK), 1) * CHUNK
    scalars = [pair_block, pair_tile, pair_cap] + list(decode or ())
    scalars = [_pad_pairs(x, npc) for x in scalars]
    qwt = jnp.pad(pair_qw.astype(jnp.float32).T,
                  ((0, 0), (0, npc - np_pairs)))
    n_scal = len(scalars)
    packed = decode is not None
    dense = tail is None
    ranking = TFIDF_COSINE if dense else tail[6]
    saturates = ranking.saturates
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    inputs = [*scalars, rows, tfs, qwt]
    in_specs = [hbm] * len(inputs)
    scratch = [pltpu.SMEM((CHUNK,), jnp.int32) for _ in scalars] + [
        pltpu.VMEM((q, CHUNK), jnp.float32),
        pltpu.VMEM((1, rows.shape[1]), rows.dtype),
        pltpu.VMEM((1, block), tfs.dtype),
        pltpu.VMEM((q, tile), jnp.float32)]
    if dense:
        out_shape = jax.ShapeDtypeStruct((n_tiles + 1, q, tile), jnp.float32)
        n_out = 1
    else:
        row_t, rank_t, qscale, k_tile, rank_blend, reducer, _ = tail
        kp = _lane_pad(k_tile)
        # [n_tiles + 1, 1, tile]: a tile's row is one slice of the
        # untiled leading dim, whatever tiling the compiler picks
        inputs += [row_t[:, None, :], rank_t[:, None, :],
                   qscale.reshape(q, 1).astype(jnp.float32)]
        in_specs += [hbm, hbm, pl.BlockSpec((q, 1), lambda i, n: (0, 0))]
        out_shape = (
            jax.ShapeDtypeStruct((n_tiles + 1, q, kp), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles + 1, q, kp), jnp.int32))
        n_out = 2
        scratch += [pltpu.VMEM((1, tile), jnp.float32),
                    pltpu.VMEM((1, tile), jnp.float32),
                    pltpu.VMEM((q, kp), jnp.float32),
                    pltpu.VMEM((q, kp), jnp.int32)]
    scratch.append(pltpu.SemaphoreType.DMA((1,)))

    def kernel(n_ref, *refs):
        s_hbm = refs[:n_scal]
        rows_hbm, tfs_hbm, qw_hbm = refs[n_scal:n_scal + 3]
        r = n_scal + 3
        if not dense:
            drow_hbm, rank_hbm, qs_ref = refs[r:r + 3]
            r += 3
        outs = refs[r:r + n_out]
        r += n_out
        s_sm = refs[r:r + n_scal]
        qw_v, row_v, tf_v, acc = refs[r + n_scal:r + n_scal + 4]
        extra = refs[r + n_scal + 4:-1]
        sem = refs[-1]

        def copy(src, dst):
            cp = pltpu.make_async_copy(src, dst, sem.at[0])
            cp.start()
            cp.wait()

        def flush(t):
            if dense:
                copy(acc, outs[0].at[t])
                return
            drow_v, rank_v, val_v, id_v = extra
            if not saturates:
                copy(drow_hbm.at[t], drow_v)
            copy(rank_hbm.at[t], rank_v)
            final = _final_from_acc(acc[...], drow_v[...], rank_v[...],
                                    qs_ref[...], rank_blend, ranking)
            vals, ids = _tile_reduce(final, t * tile, k_tile, tile, reducer)
            val_v[...] = jnp.full((q, kp), -jnp.inf, jnp.float32)
            id_v[...] = jnp.full((q, kp), -1, jnp.int32)
            val_v[:, :k_tile] = vals
            id_v[:, :k_tile] = ids
            copy(val_v, outs[0].at[t])
            copy(id_v, outs[1].at[t])

        def pair(j, cur):
            t = s_sm[1][j]
            b = s_sm[0][j]

            @pl.when(t != cur)
            def _new_tile():
                @pl.when(cur >= 0)
                def _():
                    flush(cur)
                acc[...] = jnp.zeros_like(acc)
                if saturates:
                    copy(drow_hbm.at[t], extra[0])

            copy(rows_hbm.at[pl.ds(b, 1)], row_v)
            if packed:
                copy(tfs_hbm.at[pl.ds(b >> 1, 1)], tf_v)
                tf = _unpair_tf_row(tf_v[...], b & 1)
                docs = _unpack_block_row(row_v[...], s_sm[3][j], s_sm[4][j],
                                         s_sm[5][j], block)
            else:
                copy(tfs_hbm.at[pl.ds(b, 1)], tf_v)
                tf = tf_v[...]
                docs = row_v[...]
            g = pl.multiple_of((j // LANES) * LANES, LANES)
            qwg = qw_v[:, pl.ds(g, LANES)]
            lane = jax.lax.broadcasted_iota(jnp.int32, qwg.shape, 1)
            qcol = jnp.sum(jnp.where(lane == j % LANES, qwg, 0.0), axis=1,
                           keepdims=True)                           # [Q, 1]
            acc[...] += _tile_contribution(
                docs, tf, qcol, t * tile, s_sm[2][j], tile, ranking,
                extra[0][...] if saturates else None)
            return t

        n = n_ref[0]

        def chunk(c, cur):
            base = pl.multiple_of(c * CHUNK, CHUNK)
            for src, dst in zip(s_hbm, s_sm):
                copy(src.at[pl.ds(base, CHUNK)], dst)
            copy(qw_hbm.at[:, pl.ds(base, CHUNK)], qw_v)
            return jax.lax.fori_loop(0, jnp.minimum(CHUNK, n - base), pair,
                                     cur)

        cur = jax.lax.fori_loop(0, (n + CHUNK - 1) // CHUNK, chunk,
                                jnp.int32(-1))

        @pl.when(cur >= 0)
        def _last():
            flush(cur)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(1,), in_specs=in_specs,
        out_specs=(hbm if dense else [hbm, hbm]),
        scratch_shapes=scratch)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret,
        name="pair_walk" if not saturates else f"pair_walk_{ranking.name}",
    )(_n_walk(pair_tile, pair_cap, n_tiles), *inputs)


def _check_packed_rows(packed: Array, tf_pairs: Array, block: int) -> None:
    """The packed kernels DMA the layout's stored rows as they are."""
    if packed.shape[1] % LANES or packed.shape[1] < block:
        raise ValueError(
            f"packed rows are {packed.shape[1]} lanes wide; the kernel "
            f"DMAs whole rows of a multiple of {LANES} lanes >= block "
            f"({block}): build with layouts.build_packed_csr")
    if (tf_pairs.dtype != jnp.uint32
            or 2 * tf_pairs.shape[0] < packed.shape[0]):
        raise ValueError("tfs must be the u32 pair rows of "
                         "layouts.pair_tf_rows (one row per two blocks)")


def _finish(out: Array, pair_tile: Array, n_tiles: int, tile: int,
            num_docs: int) -> Array:
    """Mask never-visited (garbage) tiles, flatten to [Q, num_docs]."""
    visited = jnp.zeros((n_tiles + 1,), jnp.bool_).at[pair_tile].set(True)
    out = jnp.where(visited[:, None, None], out, 0.0)
    q = out.shape[1]
    return out[:n_tiles].transpose(1, 0, 2).reshape(q, n_tiles * tile)[
        :, :num_docs]


def fused_score_blocked_pallas(block_docs: Array, block_tfs: Array,
                               pair_block: Array, pair_tile: Array,
                               pair_qw: Array, pair_cap: Array,
                               num_docs: int, tile: int = TILE,
                               interpret: bool | None = None) -> Array:
    """HOR path: block_docs i32[NB, B], block_tfs f32[NB, B] read in place;
    pair_* [NP] tile-sorted routing, pair_qw f32[NP, Q] per-query weight
    rows (Q padded to a multiple of 8), pair_cap i32[NP] per-pair valid
    lane count (posting-granular cap).  Returns f32[Q, num_docs]."""
    n_tiles = max(-(-num_docs // tile), 1)
    out = _walk_pairs(block_docs.astype(jnp.int32),
                      block_tfs.astype(jnp.float32), pair_block, pair_tile,
                      pair_qw, pair_cap, None, num_docs, tile,
                      block_docs.shape[1], resolve_interpret(interpret))
    return _finish(out, pair_tile, n_tiles, tile, num_docs)


def fused_score_packed_pallas(packed: Array, tf_pairs: Array,
                              pair_block: Array, pair_tile: Array,
                              pair_qw: Array, pair_cap: Array,
                              pair_bits: Array, pair_base: Array,
                              pair_count: Array,
                              num_docs: int, block: int,
                              tile: int = TILE,
                              interpret: bool | None = None) -> Array:
    """Packed path: packed u32[NB, lanes] word rows stay compressed;
    decode happens inside the scoring step.  ``tf_pairs`` holds the f16
    tfs two blocks per u32 row (``layouts.pair_tf_rows``).  Same routing
    contract as the HOR path plus per-pair (bits, base, count) decode
    scalars.  The term-sharded packed engine runs this kernel per vocab
    shard (partial scores over the GLOBAL doc space, ahead of the [D]
    psum)."""
    _check_packed_rows(packed, tf_pairs, block)
    n_tiles = max(-(-num_docs // tile), 1)
    out = _walk_pairs(packed, tf_pairs, pair_block, pair_tile,
                      pair_qw, pair_cap, (pair_bits, pair_base, pair_count),
                      num_docs, tile, block, resolve_interpret(interpret))
    return _finish(out, pair_tile, n_tiles, tile, num_docs)


def _doc_tiles(row: Array, rank: Array, n_tiles: int, tile: int):
    """Pad per-doc metadata to the tile grid (+ a zero trash tile for
    padding pairs; row 0 there marks every lane deleted)."""
    pad = n_tiles * tile - row.shape[0]
    z = jnp.zeros((1, tile), jnp.float32)
    nt = jnp.pad(row.astype(jnp.float32), (0, pad)).reshape(n_tiles, tile)
    rt = jnp.pad(rank.astype(jnp.float32), (0, pad)).reshape(n_tiles, tile)
    return jnp.concatenate([nt, z]), jnp.concatenate([rt, z])


def _finish_candidates(vals: Array, ids: Array, pair_tile: Array,
                       n_tiles: int, k_tile: int):
    """Mask never-visited (garbage) tiles to (-inf, -1), flatten the
    per-tile candidate lists tile-major to [Q, n_tiles * k_tile]."""
    visited = jnp.zeros((n_tiles + 1,), jnp.bool_).at[pair_tile].set(True)
    vals = jnp.where(visited[:, None, None], vals[..., :k_tile], -jnp.inf)
    ids = jnp.where(visited[:, None, None], ids[..., :k_tile], -1)
    q = vals.shape[1]
    return (vals[:n_tiles].transpose(1, 0, 2).reshape(q, n_tiles * k_tile),
            ids[:n_tiles].transpose(1, 0, 2).reshape(q, n_tiles * k_tile))


def _check_reducer(reducer: str, interpret: bool) -> None:
    """The bitonic reducer's j == 1 exchange reshapes the minor (lane)
    dimension (see ``_swap_stride``), which Mosaic rejects — letting it
    reach a compiled TPU lowering fails at compile time at best and
    miscompiles at worst.  Until the roll-based j == 1 stage lands,
    refuse loudly at trace time instead of trusting a loaded tuning
    table or ``REPRO_REDUCER`` to know the restriction."""
    if reducer == "bitonic" and not interpret:
        raise NotImplementedError(
            "reducer='bitonic' is interpret-only: its j == 1 lane "
            "exchange moves the minor dimension, which the Mosaic TPU "
            "compiler rejects; use reducer='successive' for compiled "
            "runs (or force it with REPRO_REDUCER=successive)")


def _topk_walk(rows, tfs, pair_block, pair_tile, pair_qw, pair_cap, decode,
               row, rank, qscale, num_docs, block, k_tile, rank_blend, tile,
               reducer, interpret, ranking):
    interp = resolve_interpret(interpret)
    _check_k_tile(k_tile, tile)
    _check_reducer(reducer, interp)
    n_tiles = max(-(-num_docs // tile), 1)
    row_t, rank_t = _doc_tiles(row, rank, n_tiles, tile)
    vals, ids = _walk_pairs(
        rows, tfs, pair_block, pair_tile, pair_qw,
        pair_cap, decode, num_docs, tile, block, interp,
        tail=(row_t, rank_t, qscale, k_tile, rank_blend, reducer, ranking))
    return _finish_candidates(vals, ids, pair_tile, n_tiles, k_tile)


def fused_topk_blocked_pallas(block_docs: Array, block_tfs: Array,
                              pair_block: Array, pair_tile: Array,
                              pair_qw: Array, pair_cap: Array,
                              row: Array, rank: Array, qscale: Array,
                              num_docs: int, k_tile: int,
                              rank_blend: float = 0.0, tile: int = TILE,
                              reducer: str = "successive",
                              interpret: bool | None = None,
                              ranking: Ranking = TFIDF_COSINE):
    """HOR candidate path: same routing contract as the dense kernel,
    plus per-doc metadata (the ranking's doc row f32[num_docs], rank
    f32[num_docs]) and per-query scales (qscale f32[Q], padding queries
    should carry 1.0).  Returns (values f32[Q, n_tiles*k_tile], ids
    i32[Q, n_tiles*k_tile]) tile-major candidate lists of FINAL scores
    — the dense [Q, num_docs] array never leaves VMEM."""
    return _topk_walk(block_docs.astype(jnp.int32),
                      block_tfs.astype(jnp.float32), pair_block,
                      pair_tile, pair_qw, pair_cap, None, row, rank, qscale,
                      num_docs, block_docs.shape[1], k_tile, rank_blend,
                      tile, reducer, interpret, ranking)


def fused_topk_packed_pallas(packed: Array, tf_pairs: Array,
                             pair_block: Array, pair_tile: Array,
                             pair_qw: Array, pair_cap: Array,
                             pair_bits: Array, pair_base: Array,
                             pair_count: Array,
                             row: Array, rank: Array, qscale: Array,
                             num_docs: int, block: int, k_tile: int,
                             rank_blend: float = 0.0, tile: int = TILE,
                             reducer: str = "successive",
                             interpret: bool | None = None,
                             ranking: Ranking = TFIDF_COSINE):
    """Packed candidate path: in-VMEM decode + per-tile top-k; only
    compressed posting words and candidates cross the kernel boundary.
    Rows as for ``fused_score_packed_pallas``."""
    _check_packed_rows(packed, tf_pairs, block)
    return _topk_walk(packed, tf_pairs, pair_block,
                      pair_tile, pair_qw, pair_cap,
                      (pair_bits, pair_base, pair_count), row, rank, qscale,
                      num_docs, block, k_tile, rank_blend, tile, reducer,
                      interpret, ranking)


def extract_tile_candidates(final: Array, tile: int, k_tile: int):
    """Pure-jnp mirror of the kernels' per-tile reduction, over a dense
    FINAL score array f32[B, num_docs] (-inf = not a hit).

    Used by the XLA lowering of the candidate engine and by the term-
    sharded scorer (whose psum forces the partial scores dense anyway).
    Returns the same tile-major (values, ids) lists as the kernels:
    per-tile ``top_k`` (ascending-id ties), ids -1 where not finite.
    """
    b, nd = final.shape
    n_tiles = max(-(-nd // tile), 1)
    f = jnp.pad(final, ((0, 0), (0, n_tiles * tile - nd)),
                constant_values=-jnp.inf)
    v, idx = jax.lax.top_k(f.reshape(b, n_tiles, tile), k_tile)
    gids = idx + (jnp.arange(n_tiles, dtype=jnp.int32) * tile)[None, :, None]
    gids = jnp.where(jnp.isfinite(v), gids, -1)
    return (v.reshape(b, n_tiles * k_tile),
            gids.reshape(b, n_tiles * k_tile))


def span_owners(offs: Array, max_pairs: int) -> Array:
    """Expand spans into pair slots: the span that owns each of
    ``max_pairs`` slots, i32[max_pairs].

    offs i32[S+1] holds the spans' running start offsets (offs[0] == 0,
    non-decreasing; a span of zero slots repeats its offset).  Slot p
    belongs to the last span u with offs[u] <= p, clamped to [0, S-1]
    (``searchsorted(offs, p, side="right") - 1``), and that is the count
    of starts offs[1:] at or below p: a prefix sum of unit marks, one
    S-wide scatter and one scan where a binary search would gather all
    ``max_pairs`` slots at each of its log2(S) levels.  Starts at or
    past the budget own no slot and are dropped.
    """
    s = offs.shape[0] - 1
    marks = jnp.zeros((max_pairs,), jnp.int32).at[offs[1:]].add(
        1, mode="drop")
    return jnp.minimum(jnp.cumsum(marks, dtype=jnp.int32), max(s - 1, 0))


def build_batched_pairs(cand_block: Array, cand_valid: Array, cand_q: Array,
                        cand_w: Array, tile_first: Array, tile_count: Array,
                        n_tiles: int, num_queries: int, max_pairs: int,
                        cand_cap: Array | None = None):
    """jnp glue: batch candidates -> deduplicated tile-sorted routing pairs.

    cand_* [S]: one entry per (query, term, block) candidate across the
    whole batch; cand_w is the query's idf weight for that block's term,
    cand_cap (optional) the number of lanes of the block the per-term
    posting ``cap`` permits (a cap cutting mid-block truncates the last
    block, matching the oracle's gather).  Blocks selected by several
    queries collapse to ONE pair per tile with a weight ROW over the
    batch (scatter-added across each query's DISTINCT terms; duplicate
    term hashes must be dedup'd upstream — ``dedup_query_hashes`` —
    or their weight double-counts here).  Returns
    (pair_block [NP], pair_tile [NP], pair_qw f32[NP, Q], pair_cap [NP],
    overflow) with NP == max_pairs; overflow counts pairs dropped
    because ``max_pairs`` was too small (0 in healthy runs — surfaced by
    the engine).
    """
    s = cand_block.shape[0]
    sentinel = jnp.int32(2**30)
    key = jnp.where(cand_valid, cand_block, sentinel)
    order = jnp.argsort(key, stable=True)        # valid blocks first, grouped
    k_s = key[order]
    q_s = cand_q[order]
    w_s = cand_w[order]
    valid_s = k_s < sentinel
    uniq = valid_s & jnp.concatenate(
        [jnp.ones(1, jnp.bool_), k_s[1:] != k_s[:-1]])
    uid = jnp.cumsum(uniq.astype(jnp.int32)) - 1  # owning unique slot (>= 0
    #                                               wherever valid_s holds)
    total_u = uid[-1] + 1 if s > 0 else jnp.int32(0)
    scat = jnp.where(valid_s, uid, s)
    ublock = jnp.zeros((s,), jnp.int32).at[
        jnp.where(uniq, uid, s)].set(k_s.astype(jnp.int32), mode="drop")
    qw = jnp.zeros((s, num_queries), jnp.float32).at[
        scat, q_s].add(w_s, mode="drop")
    if cand_cap is None:
        ucap = jnp.full((s,), jnp.iinfo(jnp.int32).max, jnp.int32)
    else:
        # a block is owned by one term, so every candidate referencing it
        # carries the same cap; scatter-max is just a safe way to pick it
        ucap = jnp.zeros((s,), jnp.int32).at[scat].max(
            cand_cap[order], mode="drop")
    uvalid = jnp.arange(s, dtype=jnp.int32) < total_u

    # expand unique blocks to their (build-time cached) tile spans
    t0 = tile_first[ublock]
    cnt = jnp.where(uvalid, tile_count[ublock], 0)
    offs = jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(cnt, dtype=jnp.int32)])
    total = offs[-1]
    p = jnp.arange(max_pairs, dtype=jnp.int32)
    owner = span_owners(offs, max_pairs)
    real = p < total
    pair_block = jnp.where(real, ublock[owner], 0)
    pair_tile = jnp.where(real, t0[owner] + (p - offs[owner]),
                          n_tiles).astype(jnp.int32)
    tile_order = jnp.argsort(pair_tile, stable=True)
    pair_qw = qw[owner[tile_order]] * real[tile_order][:, None]
    pair_cap = ucap[owner[tile_order]]
    overflow = jnp.maximum(total - max_pairs, 0)
    pair_block = pair_block[tile_order]
    pair_tile = pair_tile[tile_order]
    return pair_block, pair_tile, pair_qw, pair_cap, overflow
