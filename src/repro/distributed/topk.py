"""Distributed top-k merge (document-partitioned retrieval).

Each shard scores its local documents and keeps a local top-k; the
global answer is the top-k of the all-gathered per-shard candidates —
k·n_shards values instead of the full score vector, which is the
standard scatter-gather trick every production search tier uses.

``merge_topk_candidates`` is the pure (collective-free) core of that
merge: it is shared by the single-node fused engine's per-tile candidate
path (kernels/fused_decode_score.py reduces each doc tile to a small
candidate set in VMEM; the merge of those candidate lists is exactly a
shard merge with tiles playing the role of shards) and by the shard_map
scorers here.

Implemented with shard_map + jax.lax collectives, so it composes with
the retrieval engine in distributed/retrieval.py and with the recsys
``retrieval_cand`` cells.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, PartitionSpec as P

from repro.obs.trace import stage

Array = jax.Array


def merge_topk_candidates_host(values, ids, k: int, trace=None):
    """numpy twin of ``merge_topk_candidates`` for host-side merges.

    ``values`` / ``ids``: lists of per-source candidate arrays
    ``[..., C_i]`` (ragged last axes allowed), concatenated in source
    order.  The segmented live index merges its per-segment candidate
    lists here so the merge tier never enters jit — the set of sealed
    segments can change every batch without triggering a recompile.

    Tie-breaking matches ``jax.lax.top_k`` (earliest candidate among
    equal values): a stable descending sort keeps the first occurrence
    first, so with sources ordered by ascending doc-id range the merged
    ranking tie-breaks on lowest global doc id, like the dense oracle.

    ``trace`` optionally records a ``"merge"`` child span (of
    ``"score"``), also the profiler annotation ``serve.merge``; it
    covers the device->host copy of every source's candidates (the
    live view has already waited for the device in ``device_wait``).
    """
    with stage(trace, "merge", parent="score", sources=len(values),
               candidates=int(sum(x.shape[-1] for x in ids))):
        v = np.concatenate([np.asarray(x, np.float32) for x in values],
                           axis=-1)
        i = np.concatenate([np.asarray(x, np.int32) for x in ids], axis=-1)
        c = v.shape[-1]
        if c < k:
            pad = [(0, 0)] * (v.ndim - 1) + [(0, k - c)]
            v = np.pad(v, pad, constant_values=-np.inf)
            i = np.pad(i, pad, constant_values=-1)
        order = np.argsort(-v, axis=-1, kind="stable")[..., :k]
        return (np.take_along_axis(v, order, axis=-1),
                np.take_along_axis(i, order, axis=-1))


def canonicalize_candidates(values: Array, ids: Array
                            ) -> tuple[Array, Array]:
    """Sort candidate lists by ascending doc id on the last axis.

    ``merge_topk_candidates`` tie-breaks on the EARLIEST candidate among
    equal values, so exact-tie parity with the dense oracle needs the
    concatenated lists in ascending doc-id order.  Sources that are
    naturally ascending (per-tile lists, contiguous shard runs) get that
    for free; sources that interleave doc ranges — the mixed hor+packed
    segment-stack groups, whose group-major concatenation is NOT doc
    ordered — must canonicalize first.  Invalid candidates (id -1,
    value -inf) sort to the front, where they only ever tie other
    -inf entries, so they cannot displace a real candidate.
    """
    order = jnp.argsort(ids, axis=-1, stable=True)
    return (jnp.take_along_axis(values, order, axis=-1),
            jnp.take_along_axis(ids, order, axis=-1))


def merge_topk_candidates(values: Array, ids: Array, k: int
                          ) -> tuple[Array, Array]:
    """Pure top-k merge of candidate (value, id) lists on the last axis.

    values f32[..., C], ids i32[..., C] — candidate lists from any
    partitioning (per-tile, per-shard, all-gathered...).  Pads with
    -inf / -1 when C < k, so ``k`` may exceed the candidate count.

    Tie-breaking: ``jax.lax.top_k`` keeps the EARLIEST candidate among
    equal values, so when candidate lists are ordered by ascending doc
    id (per-tile lists concatenated tile-major, each sorted descending
    with ascending-id ties), the merged ranking tie-breaks on lowest
    doc id — bit-identical to a dense ``top_k`` over all documents.
    """
    c = values.shape[-1]
    if c < k:
        pad = [(0, 0)] * (values.ndim - 1) + [(0, k - c)]
        values = jnp.pad(values, pad, constant_values=-jnp.inf)
        ids = jnp.pad(ids, pad, constant_values=-1)
    v, pos = jax.lax.top_k(values, k)
    return v, jnp.take_along_axis(ids, pos, axis=-1)


def local_topk_merge(scores: Array, k: int, axis_name: str,
                     shard_offset: Array) -> tuple[Array, Array]:
    """Inside shard_map: scores f32[local_n] -> global (values, ids)[k].

    ``shard_offset``: scalar global id of this shard's first row.
    ``k`` may exceed the shard's local length (``jax.lax.top_k``
    requires k <= n): the local top-k is clamped to the local size and
    padded with -inf values / -1 ids before the all-gather merge.
    """
    local_n = scores.shape[-1]
    kl = min(k, local_n)
    v, i = jax.lax.top_k(scores, kl)
    gids = i + shard_offset
    if kl < k:
        v = jnp.pad(v, (0, k - kl), constant_values=-jnp.inf)
        gids = jnp.pad(gids, (0, k - kl), constant_values=-1)
    return local_candidate_merge(v, gids, k, axis_name)


def local_candidate_merge(values: Array, ids: Array, k: int,
                          axis_name: str) -> tuple[Array, Array]:
    """Inside shard_map: merge per-shard candidate lists to a global
    top-k — the thin tier over any per-shard candidate extraction
    (dense local top-k or the fused engine's per-tile candidates).
    """
    all_v = jax.lax.all_gather(values, axis_name).reshape(-1)   # [S*C]
    all_g = jax.lax.all_gather(ids, axis_name).reshape(-1)
    return merge_topk_candidates(all_v, all_g, k)


def sharded_topk(mesh: Mesh, axis: str, scores_spec: P = None):
    """Build a jit-able distributed top-k over a 1-D sharded score vector.

    Returns fn(scores f32[N]) -> (values f32[k], global_ids i32[k]).
    """
    spec = scores_spec if scores_spec is not None else P(axis)

    def make(k: int):
        @functools.partial(
            jax.shard_map, mesh=mesh, in_specs=(spec,),
            out_specs=(P(), P()), check_vma=False)
        def fn(scores):
            local = scores.reshape(-1)
            idx = jax.lax.axis_index(axis)
            off = idx * local.shape[0]
            return local_topk_merge(local, k, axis, off)
        return fn

    return make
