"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build, layouts
from repro.core.query import idf as idf_fn
from repro.kernels import ops, ref
from repro.text import corpus


def _host(seed, docs=512, vocab=400, avg=25):
    tc = corpus.generate(corpus.CorpusSpec(num_docs=docs, vocab=vocab,
                                           avg_distinct=avg, seed=seed))
    return build.bulk_build(tc)


@pytest.mark.parametrize("seed,block,tile", [(0, 16, 128), (1, 32, 256),
                                             (2, 64, 128)])
@pytest.mark.slow
def test_posting_score_sweep(seed, block, tile):
    host = _host(seed)
    hor = layouts.build_blocked(host, block=block)
    qh = corpus.sample_query_terms(host.df, host.term_hashes, 1, 4,
                                   num_docs=host.num_docs, seed=seed)[0]
    tids = hor.lookup_terms(jnp.asarray(qh))
    w = idf_fn(hor.term_df(tids), host.num_docs)
    kw = dict(max_blocks_per_term=hor.max_blocks_per_term, max_pairs=8192)
    s_pl = ops.blocked_query_scores(hor, tids, w, tile=tile,
                                    backend="pallas", **kw)
    s_x = ops.blocked_query_scores(hor, tids, w, backend="xla", **kw)
    np.testing.assert_allclose(np.asarray(s_pl), np.asarray(s_x),
                               rtol=1e-5, atol=1e-6)


def test_posting_score_pair_overflow_counter():
    from repro.kernels.posting_score import build_pairs
    host = _host(3)
    hor = layouts.build_blocked(host, block=16)
    tfirst, tcount, n_tiles = ops.routing_spans(hor, 64)
    sel = jnp.arange(8, dtype=jnp.int32)
    valid = jnp.ones(8, bool)
    w = jnp.ones(8)
    *_, ovf = build_pairs(sel, valid, w, tfirst, tcount, n_tiles,
                          max_pairs=2)
    assert int(ovf) > 0      # too-small pair budget is REPORTED, not silent


@pytest.mark.parametrize("seed,block", [(0, 16), (1, 32), (2, 128)])
@pytest.mark.slow
def test_packed_unpack_sweep(seed, block):
    host = _host(seed)
    packed = layouts.build_packed_csr(host, block=block)
    d_pl = ops.unpack_postings(packed, backend="pallas")
    d_x = ops.unpack_postings(packed, backend="xla")
    assert (np.asarray(d_pl) == np.asarray(d_x)).all()
    # decoded ids reproduce the source postings exactly
    order = np.argsort(host.term_hashes, kind="stable")
    t0 = order[0]
    s, e = host.offsets[t0], host.offsets[t0 + 1]
    b0 = int(packed.block_offsets[0])
    got = np.asarray(d_pl[b0])[:e - s]
    np.testing.assert_array_equal(got[:min(block, e - s)],
                                  host.doc_ids[s:s + min(block, e - s)])


def _np_unpack_block(words, bits, base, count, block):
    """Independent numpy oracle for the bit-packed block decoder,
    including the kernel's exact int32 wrap-around semantics."""
    mask = (1 << bits) - 1 if bits < 32 else 0xFFFFFFFF
    deltas = np.zeros(block, np.int64)
    for lane in range(block):
        bitpos = lane * bits
        wi, off = divmod(bitpos, 32)
        lo = int(words[wi]) >> off
        hi = (int(words[min(wi + 1, len(words) - 1)]) << (32 - off)) \
            if off else 0
        deltas[lane] = (lo | hi) & mask
    docs = int(base) + np.cumsum(deltas)
    docs = ((docs + 2**31) % 2**32 - 2**31).astype(np.int32)  # i32 wrap
    return np.where(np.arange(block) < count, docs, -1)


@pytest.mark.parametrize("bits", list(range(4, 33)))
@pytest.mark.parametrize("block", [16, 128])
@pytest.mark.slow
def test_packed_unpack_bit_width_sweep(bits, block):
    """Cross-block bleed guard: the kernel's hi-word fetch clamps to the
    LAST WORD OF THE BLOCK, so every bit width whose final lane lands on
    a word boundary must still decode exactly — swept bits 4..32 against
    an independent numpy unpacker over adversarial random words."""
    rng = np.random.default_rng(bits * 1000 + block)
    nb = 8
    wpb = (block * bits + 31) // 32
    # random words with all-ones high bytes mixed in: if the clamped
    # hi-word fetch ever bled into a neighbouring lane, these would show
    words = rng.integers(0, 2**32, size=(nb, wpb), dtype=np.uint32)
    words[:, -1] |= np.uint32(0xFF000000)
    bits_a = np.full(nb, bits, np.int32)
    base_a = rng.integers(-5, 1000, size=nb).astype(np.int32)
    count_a = rng.integers(1, block + 1, size=nb).astype(np.int32)
    from repro.kernels.packed_postings import unpack_blocks_pallas
    got = np.asarray(unpack_blocks_pallas(
        jnp.asarray(words), jnp.asarray(bits_a), jnp.asarray(base_a),
        jnp.asarray(count_a), block, interpret=True))
    want = np.stack([_np_unpack_block(words[i], bits, base_a[i],
                                      count_a[i], block)
                     for i in range(nb)])
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("bits", [4, 7, 11, 13, 17, 23, 29, 31, 32])
@pytest.mark.slow
def test_pack_roundtrip_bit_width_sweep(bits):
    """pack -> kernel unpack is the identity for every bit width,
    including widths whose final lane straddles a u32 word boundary."""
    from repro.kernels.packed_postings import unpack_blocks_pallas
    rng = np.random.default_rng(bits)
    block = 128
    hi = min(1 << bits, 2**24)        # keep cumsum inside int32
    deltas = rng.integers(0, hi, size=block).astype(np.int64)
    deltas[-1] = hi - 1               # force the last lane's full width
    words = layouts._pack_block_np(deltas, bits, block)[None, :]
    got = np.asarray(unpack_blocks_pallas(
        jnp.asarray(words.astype(np.uint32)),
        jnp.asarray([bits], np.int32), jnp.asarray([0], np.int32),
        jnp.asarray([block], np.int32), block, interpret=True))[0]
    np.testing.assert_array_equal(got, np.cumsum(deltas).astype(np.int32))


@pytest.mark.parametrize("v,d,b,h,dtype", [
    (100, 8, 32, 4, jnp.float32),
    (500, 16, 64, 7, jnp.float32),
    (50, 32, 16, 2, jnp.bfloat16),
])
@pytest.mark.slow
def test_embedding_bag_sweep(v, d, b, h, dtype):
    rng = np.random.default_rng(v + b)
    tab = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32)).astype(dtype)
    idx = jnp.asarray(rng.integers(-1, v, size=(b, h)).astype(np.int32))
    got = ops.embedding_bag(tab, idx, tile_b=min(16, b), backend="pallas")
    want = ops.embedding_bag(tab, idx, backend="xla")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-6)


@pytest.mark.parametrize("n,k,d,nsrc", [(32, 5, 8, 100), (64, 9, 16, 64)])
@pytest.mark.slow
def test_pna_multi_agg_sweep(n, k, d, nsrc):
    rng = np.random.default_rng(n + k)
    feats = jnp.asarray(rng.normal(size=(nsrc, d)).astype(np.float32))
    nbr = jnp.asarray(rng.integers(-1, nsrc, size=(n, k)).astype(np.int32))
    got = ops.pna_multi_agg(feats, nbr, tile_n=min(32, n), backend="pallas")
    want = ops.pna_multi_agg(feats, nbr, backend="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("causal,window,hq,hkv,s,d,dtype", [
    (True, 0, 4, 2, 64, 16, jnp.float32),
    (True, 24, 4, 4, 64, 16, jnp.float32),
    (False, 0, 2, 1, 32, 32, jnp.float32),
    (True, 16, 8, 2, 64, 16, jnp.bfloat16),
])
@pytest.mark.slow
def test_flash_attention_sweep(causal, window, hq, hkv, s, d, dtype):
    rng = np.random.default_rng(s + hq)
    q = jnp.asarray(rng.normal(size=(2, hq, s, d)).astype(np.float32)).astype(dtype)
    k = jnp.asarray(rng.normal(size=(2, hkv, s, d)).astype(np.float32)).astype(dtype)
    v = jnp.asarray(rng.normal(size=(2, hkv, s, d)).astype(np.float32)).astype(dtype)
    got = ops.attention(q, k, v, causal=causal, window=window,
                        backend="pallas", block_q=32, block_k=32)
    want = ops.attention(q, k, v, causal=causal, window=window,
                         backend="xla")
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)


def test_flash_matches_chunked_model_attention():
    """The Pallas kernel agrees with the model's chunked-XLA attention."""
    from repro.models.attention import chunked_attention
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 4, 64, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 2, 64, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 2, 64, 16)).astype(np.float32))
    for window in (0, 24):
        a = chunked_attention(q, k, v, causal=True, window=window, chunk=16)
        b = ops.attention(q, k, v, causal=True, window=window,
                          backend="pallas", block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-5)


def test_compile_cache_dir_is_fixed(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins untouched; otherwise the cache
    sits at ``<checkout>/.jax_cache`` (no temp name, pid or time)."""
    import os

    from repro.kernels.runtime import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        checkout = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                ".."))
        want = os.path.join(checkout, ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_f16_bits_to_f32_exact_for_every_pattern():
    """The kernels' integer f16 widening equals the f16 -> f32 cast for
    all 65,536 bit patterns (NaNs stay NaN)."""
    from repro.kernels.fused_decode_score import _f16_bits_to_f32
    bits = np.arange(1 << 16, dtype=np.uint16)
    want = bits.view(np.float16).astype(np.float32)
    got = np.asarray(_f16_bits_to_f32(jnp.asarray(bits.astype(np.int32))))
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(got[~nan].view(np.uint32),
                                  want[~nan].view(np.uint32))


def test_unpair_tf_row_matches_layout_decode():
    """The kernels' per-row tf decode equals ``layouts.unpair_tfs`` for
    both halves of every pair row."""
    from repro.kernels.fused_decode_score import _unpair_tf_row
    rng = np.random.default_rng(5)
    tfs = rng.integers(0, 3000, (7, 128)).astype(np.float16)
    pairs = jnp.asarray(layouts.pair_tf_rows(tfs))
    for b in range(7):
        got = _unpair_tf_row(pairs[b >> 1][None], jnp.int32(b & 1))[0]
        want = layouts.unpair_tfs(pairs, jnp.int32(b))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(want),
                                      tfs[b].astype(np.float32))
