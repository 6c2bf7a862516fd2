"""Stand up the system under test from a configuration and a corpus.

The live index is restored from a snapshot that the benchmark writes
itself (``repro.serve.snapshot`` format 3): each sealed segment is
built straight at the doc count the configuration plans, and the last
documents sit in the delta, as streaming ingest leaves them.  No
compaction merge runs.  Each segment's layout is what the program's
default ``LayoutCostModel`` chooses for it.  The configuration's ranking
(``bench/rankings/<ranking>.py``) adds its arrays and meta keys to the
snapshot (``snapshot_part``) and sets the restored index up
(``after_restore``).
"""
from __future__ import annotations

import json

import numpy as np


def plan(config: dict) -> tuple[list[tuple[int, int]], int]:
    """([(doc_base, doc_span)] of the sealed segments, delta docs)."""
    spans = [int(s) for s in config["segments"]]
    delta = int(config["delta_docs"])
    if sum(spans) + delta != int(config["num_docs"]):
        raise ValueError("segments + delta must hold every document")
    bases = np.concatenate([[0], np.cumsum(spans)[:-1]]).astype(int)
    return list(zip(bases.tolist(), spans)), delta


def snapshot_state(config: dict, corpus, seed: int, ranking) -> dict:
    """The corpus as a snapshot of a live index, in the program's format,
    with the ranking's part."""
    from repro.core import layouts, size_model

    segments, delta = plan(config)
    n = corpus.num_docs
    policy = size_model.LayoutCostModel()
    seg_meta, state = [], {}
    for i, (base, span) in enumerate(segments):
        lo, hi = corpus.doc_ptr[base], corpus.doc_ptr[base + span]
        terms = corpus.terms[lo:hi]
        n_terms = int(np.count_nonzero(np.bincount(terms,
                                                   minlength=corpus.vocab)))
        size_class = layouts.size_class(span, base=layouts.ROUTE_TILE)
        layout, reason = size_model.resolve_layout(
            None, policy, size_model.SegmentStats(
                num_docs=span, num_postings=int(hi - lo),
                num_terms=n_terms), "hor", size_class=size_class)
        seg_meta.append({"doc_base": base, "doc_span": span,
                         "n_postings": int(hi - lo), "layout": layout,
                         "size_class": int(size_class),
                         "num_terms": n_terms, "chooser_reason": reason,
                         "band_cut": 0})
        state[f"seg{i}_doc_of"] = np.repeat(
            np.arange(span, dtype=np.int32),
            np.diff(corpus.doc_ptr[base:base + span + 1]))
        state[f"seg{i}_terms"] = terms
        state[f"seg{i}_tfs"] = corpus.tfs[lo:hi]
    d0 = n - delta
    rng = np.random.default_rng([0x72616E6B, int(seed)])
    arrays, meta_keys = ranking.snapshot_part(corpus)
    meta = {
        "version": 3, "live_docs": n, "epoch": 0, "seal_layout": "hor",
        "delta": {"doc_cap": int(config["delta_doc_capacity"]),
                  "post_cap": int(config["delta_posting_capacity"]),
                  "doc_base": d0, "n_docs": delta},
        "policy": {"size_ratio": 4.0, "min_run": 4},
        "rng_state": rng.bit_generator.state,
        "stats": {},
        "layout_policy": policy.to_dict(),
        "segments": seg_meta,
        **meta_keys,
    }
    state.update({
        "meta": np.frombuffer(json.dumps(meta).encode(), np.uint8),
        "hashes": corpus.hashes,
        "df": corpus.df(),
        "live": np.ones(n, bool),
        "rank": (rng.random(n) * 1e-3).astype(np.float32),
        "norm": np.zeros(n, np.float32),
        "delta_terms": corpus.terms[corpus.doc_ptr[d0]:],
        "delta_tfs": corpus.tfs[corpus.doc_ptr[d0]:],
        "delta_lens": np.diff(corpus.doc_ptr[d0:]),
        **arrays,
    })
    return state


def build_index(config: dict, corpus, seed: int, ranking):
    """The live index, set up for the ranking, and its view pinned."""
    from repro.serve import snapshot

    si = snapshot.restore_segmented(snapshot_state(config, corpus, seed,
                                                   ranking))
    ranking.after_restore(si)
    si.view()
    return si


def server(index, config: dict, mix: dict, traced: bool):
    from repro.serve.server import QueryServer, ServerConfig

    return QueryServer(index, ServerConfig(
        batch_size=int(config["batch_size"]),
        n_terms_budget=int(config["n_terms_budget"]), k=int(config["k"]),
        cache_capacity=int(mix["cache_capacity"]),
        trace_sample=1 if traced else 0))


def device_bytes() -> int:
    """Bytes the device allocators hold (``bytes_in_use`` of
    ``memory_stats``, summed over the devices).  A backend without
    allocator statistics (the CPU) sums the arrays JAX holds instead."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    if all("bytes_in_use" in s for s in stats):
        return sum(int(s["bytes_in_use"]) for s in stats)
    return live_array_bytes()


def live_array_bytes() -> int:
    """Bytes of every array JAX holds on the device."""
    import jax

    return sum(int(a.nbytes) for a in jax.live_arrays())
