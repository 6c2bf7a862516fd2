"""Pallas TPU kernel: blocked posting-list scoring (q_occ + accumulate).

The paper's query evaluation bottleneck is streaming posting lists and
accumulating per-document scores.  A GPU implementation would use atomic
scatter-adds; TPUs have no hardware scatter, so we ADAPT (DESIGN.md §2):

  * postings live in the HOR/BlockedIndex layout: 128-lane blocks with
    per-block doc-id min/max — each block is one aligned VMEM tile DMA;
  * the scatter-add becomes a ONE-HOT MATMUL on the MXU: a block's 128
    postings are compared against a 512-wide doc tile (VPU compare) and
    contracted `w[1,128] @ onehot[128,512]` into the tile accumulator;
  * block -> doc-tile routing is data-dependent, so it is precomputed as
    a (block, tile) pair list fed through SCALAR PREFETCH; pairs are
    sorted by tile so each output tile is resident in VMEM for one
    contiguous run of grid steps (revisit-accumulation pattern), with the
    score buffer zero-initialized through input/output aliasing.

HBM traffic: each selected posting block is read exactly once per tile it
overlaps (high-df terms overlap ~1 tile per block); the PR/COO layout by
contrast must gather scattered heap tuples.  This kernel is the TPU
restatement of the paper's claim that layout determines I/O.

Fused-engine design (see ``kernels/fused_decode_score.py``, the batched
successor of this kernel):

  * PAIR ROUTING — the (block, tile) expansion used to be derived from
    ``block_min``/``block_max`` per query inside ``build_pairs``; the
    span table is a pure function of the immutable index, so it is now a
    BUILD-TIME cache (``tile_first``/``tile_count`` on BlockedIndex and
    PackedCsrIndex, plus static ``route_pairs_max``/``route_span_max``
    pair budgets).  ``build_pairs`` only does the per-query cumsum /
    ``span_owners`` expansion over those cached spans.
  * BATCH TILING — the fused kernel widens this kernel's ``[1, tile]``
    accumulator to ``[Q, tile]``: routing pairs are deduplicated across a
    batch of queries and carry a per-query weight ROW, so a hot posting
    block is DMA'd once and a rank-1 MXU update serves every query that
    touches it.
  * HBM-BYTES ACCOUNTING — per batch, posting bytes =
    sum over unique (block, tile) pairs of the block payload:
    ``4*ceil(128*bits/32) + 2*128`` B packed vs ``8*128`` B unpacked HOR,
    i.e. the compressed layout streams <= 0.5x the bytes (measured per
    query by ``benchmarks/roofline.py``).  The fused kernel never writes
    decompressed postings back to HBM — decode happens in VMEM inside
    the scoring step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_decode_score import span_owners
from repro.kernels.runtime import resolve_interpret

Array = jax.Array

TILE = 512  # doc-space tile width (4 × 128 lanes)


def _score_kernel(pair_block, pair_tile, pair_w, pair_first,  # prefetch (SMEM)
                  docs_ref, tfs_ref,                  # inputs (VMEM blocks)
                  out_ref,                            # output tile accumulator
                  *, tile: int):
    i = pl.program_id(0)

    # First pair touching this tile zero-initializes its VMEM block; later
    # pairs (sorted by tile -> contiguous run) accumulate in place.
    @pl.when(pair_first[i] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tile_base = pair_tile[i] * tile
    docs = docs_ref[0, :]                               # i32[B]
    local = docs - tile_base
    inb = (docs >= 0) & (local >= 0) & (local < tile)
    w = tfs_ref[0, :] * pair_w[i]                       # f32[B]
    w = jnp.where(inb, w, 0.0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (docs.shape[0], tile), 1)
    onehot = (local[:, None] == lane).astype(jnp.float32)   # [B, tile]
    contrib = jnp.dot(w[None, :], onehot,
                      preferred_element_type=jnp.float32)   # [1, tile] (MXU)
    out_ref[...] += contrib


def posting_score_pallas(block_docs: Array, block_tfs: Array,
                         pair_block: Array, pair_tile: Array, pair_w: Array,
                         num_docs: int, tile: int = TILE,
                         interpret: bool | None = None) -> Array:
    """Run the scoring kernel.

    block_docs i32[NB, B], block_tfs f32[NB, B]: the index's posting blocks
    (read in place — no per-query copy).
    pair_* [NP]: (block, tile, weight) routing triples, SORTED by tile;
    padding pairs use tile == n_tiles (trash row) and weight 0.
    """
    nb, b = block_docs.shape
    n_tiles = -(-num_docs // tile)
    np_pairs = pair_block.shape[0]
    pair_first = jnp.concatenate(
        [jnp.ones(1, jnp.int32),
         (pair_tile[1:] != pair_tile[:-1]).astype(jnp.int32)])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(np_pairs,),
        in_specs=[
            pl.BlockSpec((1, b), lambda i, pb, pt, pw, pf: (pb[i], 0)),
            pl.BlockSpec((1, b), lambda i, pb, pt, pw, pf: (pb[i], 0)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i, pb, pt, pw, pf: (pt[i], 0)),
    )
    out = pl.pallas_call(
        functools.partial(_score_kernel, tile=tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles + 1, tile), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(pair_block, pair_tile, pair_w, pair_first, block_docs, block_tfs)
    # Tiles never visited by any pair hold garbage -> mask them to zero.
    visited = jnp.zeros((n_tiles + 1,), jnp.bool_).at[pair_tile].set(True)
    out = jnp.where(visited[:, None], out, 0.0)
    return out[:n_tiles].reshape(-1)[:num_docs]


def build_pairs(sel_blocks: Array, sel_valid: Array, sel_w: Array,
                tile_first: Array, tile_count: Array, n_tiles: int,
                max_pairs: int):
    """jnp glue: expand selected blocks into tile-sorted routing pairs.

    sel_blocks i32[S] global block ids for the query's terms,
    sel_valid bool[S], sel_w f32[S] per-block term weight (idf).
    tile_first/tile_count i32[NB] are the index's BUILD-TIME routing
    cache (block -> doc-tile span) — see ``ops.routing_spans``.
    Returns (pair_block, pair_tile, pair_w, overflow) with static size
    ``max_pairs``; ``overflow`` counts dropped pairs (0 in healthy runs).
    """
    safe = jnp.maximum(sel_blocks, 0)
    t0 = tile_first[safe]
    span = jnp.where(sel_valid, tile_count[safe], 0)
    offs = jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(span, dtype=jnp.int32)])
    total = offs[-1]
    p = jnp.arange(max_pairs, dtype=jnp.int32)
    owner = span_owners(offs, max_pairs)
    real = p < total
    tile_id = t0[owner] + (p - offs[owner])
    pair_block = jnp.where(real, safe[owner], 0)
    pair_tile = jnp.where(real, tile_id, n_tiles).astype(jnp.int32)
    pair_w = jnp.where(real, sel_w[owner], 0.0)
    order = jnp.argsort(pair_tile, stable=True)
    overflow = jnp.maximum(total - max_pairs, 0)
    return (pair_block[order], pair_tile[order], pair_w[order], overflow)
