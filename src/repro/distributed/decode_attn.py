"""Explicit split-K (flash-decoding style) distributed decode attention.

The GSPMD path (models/attention.decode_attention with a seq-sharded
cache) lets XLA derive the collectives; this shard_map version makes the
schedule EXPLICIT — each shard computes attention over its cache slice
with a local max/sum, and the combine is three small psums (max-shifted
numerator, denominator, running max), i.e. log-sum-exp merging — so the
wire cost is O(B·H·D) per step regardless of sequence length.

Used by the long_500k serve path and by tests as the oracle-checked
reference for the GSPMD lowering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array

NEG_INF = -1e30


def _local_partial(q, k_loc, v_loc, kpos, cache_len, window):
    """Per-shard partial attention: returns (m, num, den)."""
    b, hq, _, d = q.shape
    hkv = k_loc.shape[1]
    group = hq // hkv
    scale = d ** -0.5
    qg = q.reshape(b, hkv, group, d)
    s = jnp.einsum("bhgd,bhsd->bhgs", qg.astype(jnp.float32),
                   k_loc.astype(jnp.float32)) * scale
    valid = kpos[None, :] <= cache_len[:, None]
    w = jnp.asarray(window, jnp.int32)
    valid &= (w <= 0) | (kpos[None, :] > cache_len[:, None] - w)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                  # [b,hkv,g]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    den = p.sum(axis=-1)                                     # [b,hkv,g]
    num = jnp.einsum("bhgs,bhsd->bhgd", p,
                     v_loc.astype(jnp.float32))
    return m, num, den


def splitk_decode_attention(mesh: Mesh, axis: str):
    """Build fn(q [B,Hq,1,D], k_cache/v_cache [B,Hkv,S,D] seq-sharded,
    cache_len i32[B], window) -> [B,Hq,1,D]."""

    def fn(q, k_cache, v_cache, cache_len, window: int = 0):
        seq = k_cache.shape[2]
        n = int(mesh.shape[axis])
        local = seq // n

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(), P(None, None, axis, None),
                      P(None, None, axis, None), P()),
            out_specs=P(), check_vma=False)
        def inner(qq, kk, vv, cl):
            idx = jax.lax.axis_index(axis)
            kpos = idx * local + jnp.arange(local, dtype=jnp.int32)
            m, num, den = _local_partial(qq, kk, vv, kpos, cl, window)
            g_m = jax.lax.pmax(m, axis)
            corr = jnp.exp(m - g_m)
            num = num * corr[..., None]
            den = den * corr
            g_num = jax.lax.psum(num, axis)
            g_den = jax.lax.psum(den, axis)
            out = g_num / jnp.maximum(g_den, 1e-30)[..., None]
            b, hkv, group, d = out.shape
            return out.reshape(b, hkv * group, 1, d)

        return inner(q, k_cache, v_cache, cache_len)

    return fn
