"""Compile the served path's kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles for one chip of a
``v5e:2x2`` topology that needs no device, so Mosaic's refusals
(block shapes, unaligned slices, SMEM and VMEM budgets, unsupported
primitives) surface here instead of on the chip.  Shapes are those of
the paper-scale collection's largest packed segment (the 1m tier of
``benchmarks/campaign.py`` as ``chip_smoke.py`` builds it).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import layouts
from repro.kernels import fused_decode_score as fds
from repro.kernels import ops

# The 1m tier's largest sealed segment (985,099 docs bulk-built through
# SegmentedIndex.from_host under the default LayoutCostModel): its size
# class, padded block count, padded vocabulary and the pair budgets.
DOCS = 1_048_576            # layouts.size_class of the segment's doc span
BLOCKS = 524_288            # padded block count of that class
VOCAB = 65_536              # padded vocabulary (w_pad) of the segment
WHOLE_INDEX_PAIRS = 134_217_728   # ops.scaled_pairs_budget(segment)
SERVED_PAIRS = 655_296      # ops.default_max_pairs(segment, 8, 8, DOCS)
SPAN_MAX = 2_048            # route_span_max of the segment
# (blocks, words per block, docs) of the two packed segments at that
# tier: the 1m-class bulk segment and the 16k-class sealed tail
PACKED_SHAPES = [(BLOCKS, 72, DOCS), (65_536, 56, 16_384)]
Q, TILE, K_TILE, BLOCK = 8, 512, 16, layouts.BLOCK
BAND_WORDS = 40             # a packed band's narrower stride (any <= 72)
HBM_BYTES = 16 * 10**9      # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    # only a missing TPU compiler skips (CI installs jax[cpu]); any
    # other failure to describe the topology is a failure
    pytest.importorskip("libtpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def compiled_kernels(monkeypatch, no_compile_cache):
    """Steer ``interpret=None`` to the Mosaic lowering (the default
    backend here is the CPU, which would pick the interpreter)."""
    monkeypatch.setattr(fds, "resolve_interpret",
                        lambda interpret: False if interpret is None
                        else interpret)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled.memory_analysis()


def _pair_args(sds, n_pairs, decode=False):
    i32 = jnp.int32
    args = [sds((n_pairs,), i32), sds((n_pairs,), i32),
            sds((n_pairs, Q), jnp.float32), sds((n_pairs,), i32)]
    return args + ([sds((n_pairs,), i32)] * 3 if decode else [])


def _sds(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _packed_rows(sds, blocks, wpb):
    """The stored packed rows: lane-padded words + u32 tf pairs."""
    return [sds((blocks, layouts.lane_width(wpb)), jnp.uint32),
            sds((-(-blocks // 2), BLOCK), jnp.uint32)]


def _per_block(sds, blocks):
    return {n: sds((blocks,), jnp.int32) for n in (
        "block_min", "block_max", "tile_first", "tile_count")}


def _packed_index(sds, blocks, wpb, docs_table):
    i32, u32 = jnp.int32, jnp.uint32
    words, tf_pairs = _packed_rows(sds, blocks, wpb)
    return layouts.PackedCsrIndex(
        sorted_hash=sds((VOCAB,), u32), df=sds((VOCAB,), i32),
        block_offsets=sds((VOCAB + 1,), i32),
        block_bits=sds((blocks,), i32), block_base=sds((blocks,), i32),
        block_count=sds((blocks,), i32), packed=words, tf_pairs=tf_pairs,
        docs=docs_table, max_posting_len=DOCS, words_per_block=wpb,
        route_tile=TILE, route_pairs_max=WHOLE_INDEX_PAIRS,
        route_span_max=SPAN_MAX, **_per_block(sds, blocks))


def _docs_table(sds):
    return layouts.DocTable(norm=sds((DOCS,), jnp.float32),
                            rank=sds((DOCS,), jnp.float32))


def _held_bytes(compiled):
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes)


@pytest.mark.parametrize("blocks,wpb,docs", PACKED_SHAPES)
def test_packed_candidate_kernel_compiles(one_chip, compiled_kernels,
                                          blocks, wpb, docs):
    """At the whole-index budget: SMEM holds a chunk of the routing
    pairs, not all of them, so no budget is too large to compile."""
    sds = _sds(one_chip)
    args = [*_packed_rows(sds, blocks, wpb),
            *_pair_args(sds, WHOLE_INDEX_PAIRS, decode=True),
            sds((docs,), jnp.float32), sds((docs,), jnp.float32),
            sds((Q,), jnp.float32)]
    _compile(functools.partial(fds.fused_topk_packed_pallas, num_docs=docs,
                               block=BLOCK, k_tile=K_TILE, tile=TILE),
             *args)


def test_hor_candidate_kernel_compiles(one_chip, compiled_kernels):
    sds = _sds(one_chip)
    args = [sds((BLOCKS, BLOCK), jnp.int32),
            sds((BLOCKS, BLOCK), jnp.float32),
            *_pair_args(sds, WHOLE_INDEX_PAIRS),
            sds((DOCS,), jnp.float32), sds((DOCS,), jnp.float32),
            sds((Q,), jnp.float32)]
    _compile(functools.partial(fds.fused_topk_blocked_pallas,
                               num_docs=DOCS, k_tile=K_TILE, tile=TILE),
             *args)


@pytest.mark.parametrize("layout", ["hor", "packed"])
def test_bm25_candidate_kernel_compiles(one_chip, compiled_kernels, layout):
    """The walk specialised for BM25 (``pair_walk_bm25``): the tile's
    length row DMA'd when the walk enters a tile, and the per-posting
    saturation, at the served pair budget."""
    from repro.core.ranking import bm25
    sds = _sds(one_chip)
    docs_rows = [sds((DOCS,), jnp.float32), sds((DOCS,), jnp.float32),
                 sds((Q,), jnp.float32)]
    if layout == "packed":
        blocks, wpb, docs = PACKED_SHAPES[0]
        args = [*_packed_rows(sds, blocks, wpb),
                *_pair_args(sds, SERVED_PAIRS, decode=True), *docs_rows]
        fn = functools.partial(fds.fused_topk_packed_pallas, num_docs=docs,
                               block=BLOCK, k_tile=K_TILE, tile=TILE,
                               ranking=bm25(0.9, 0.4))
    else:
        args = [sds((BLOCKS, BLOCK), jnp.int32),
                sds((BLOCKS, BLOCK), jnp.float32),
                *_pair_args(sds, SERVED_PAIRS), *docs_rows]
        fn = functools.partial(fds.fused_topk_blocked_pallas, num_docs=DOCS,
                               k_tile=K_TILE, tile=TILE,
                               ranking=bm25(0.9, 0.4))
    lowered = jax.jit(fn).lower(*args)
    assert "pair_walk_bm25" in lowered.as_text()
    assert lowered.compile().memory_analysis() is not None


@pytest.mark.parametrize("layout", ["hor", "packed"])
def test_dense_kernel_compiles(one_chip, compiled_kernels, layout):
    sds = _sds(one_chip)
    if layout == "packed":
        args = [*_packed_rows(sds, BLOCKS, 72),
                *_pair_args(sds, SERVED_PAIRS, decode=True)]
        fn = functools.partial(fds.fused_score_packed_pallas,
                               num_docs=DOCS, block=BLOCK, tile=TILE)
    else:
        args = [sds((BLOCKS, BLOCK), jnp.int32),
                sds((BLOCKS, BLOCK), jnp.float32),
                *_pair_args(sds, SERVED_PAIRS)]
        fn = functools.partial(fds.fused_score_blocked_pallas,
                               num_docs=DOCS, tile=TILE)
    _compile(fn, *args)


def test_served_segment_engine_fits_one_chip(one_chip, compiled_kernels):
    """The whole per-segment engine the QueryServer dispatches (lookup,
    routing, kernel, candidate flattening) at the served batch budget,
    and what it holds on the device fits one chip's HBM."""
    sds = _sds(one_chip)
    ix = _packed_index(sds, BLOCKS, 72, _docs_table(sds))
    budget = ops.default_max_pairs(ix, Q, 8, DOCS, TILE)
    assert budget == SERVED_PAIRS
    lowered = ops.fused_segment_topk.lower(
        ix, sds((Q, 8), jnp.uint32), sds((Q, 8), jnp.float32),
        sds((Q,), jnp.float32), sds((), jnp.int32),
        k_tile=K_TILE, cap=DOCS, max_pairs=budget, tile=TILE)
    assert "tpu_custom_call" in lowered.as_text()
    held = _held_bytes(lowered.compile())
    assert held < HBM_BYTES, held


def test_banded_segment_engine_fits_one_chip(one_chip, compiled_kernels):
    """The banded engine (one dense fused launch per band, partials
    summed) at the 1m class, each band at the class's full block count
    (an upper bound on either band), with the per-band batch budgets
    the live index passes: compiles, and fits one chip's HBM."""
    sds = _sds(one_chip)
    docs = _docs_table(sds)
    packed = _packed_index(sds, BLOCKS, BAND_WORDS, docs)
    hor = layouts.BlockedIndex(
        sorted_hash=packed.sorted_hash, df=sds((VOCAB,), jnp.int32),
        block_offsets=sds((VOCAB + 1,), jnp.int32),
        block_docs=sds((BLOCKS, BLOCK), jnp.int32),
        block_tfs=sds((BLOCKS, BLOCK), jnp.float32), docs=docs,
        max_posting_len=DOCS, max_blocks_per_term=DOCS // BLOCK,
        route_tile=TILE, route_pairs_max=WHOLE_INDEX_PAIRS,
        route_span_max=SPAN_MAX, **_per_block(sds, BLOCKS))
    ix = layouts.BandedCsrIndex(packed=packed, hor=hor)
    mp_p, mp_h, cap_p, cap_h = ops.banded_pairs_budgets(ix, Q, 8, DOCS,
                                                        TILE)
    # each band is bounded by the batch's term slots, not the index
    assert mp_p == mp_h == SERVED_PAIRS
    lowered = ops.fused_segment_banded_topk.lower(
        ix, sds((Q, 8), jnp.uint32), sds((Q, 8), jnp.float32),
        sds((Q,), jnp.float32), sds((), jnp.int32), k_tile=K_TILE,
        cap_packed=cap_p, cap_hor=cap_h, max_pairs_packed=mp_p,
        max_pairs_hor=mp_h, tile=TILE)
    assert lowered.as_text().count("tpu_custom_call") >= 2
    held = _held_bytes(lowered.compile())
    assert held < HBM_BYTES, held


def test_delta_scorer_compiles(one_chip, no_compile_cache):
    """The delta scan at the capacities a QueryServer's index gets by
    default (SegmentedIndex delta: 512 docs, 64 postings each; a batch
    of 8 queries x 8 term slots)."""
    from repro.core.live_index import _delta_candidates
    sds = _sds(one_chip)
    docs, posts = 512, 512 * 64
    i32, f32 = jnp.int32, jnp.float32
    compiled = _delta_candidates.lower(
        sds((posts,), i32), sds((posts,), f32), sds((posts,), i32),
        sds((docs,), f32), sds((docs,), f32), sds((Q, 8), i32),
        sds((Q, 8), f32), sds((Q,), f32), sds((), i32),
        k_tile=K_TILE, tile=TILE).compile()
    assert compiled.memory_analysis() is not None
