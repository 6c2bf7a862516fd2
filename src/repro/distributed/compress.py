"""Gradient compression: int8 all-to-all reduce-scatter with error feedback.

Wire math per device for an N-element f32 gradient over S shards:
  plain ring all-reduce   ~ 2·4N bytes
  int8 a2a reduce-scatter ~ N bytes (a2a) + N bytes (gather) = 2N bytes
-> ~4x fewer ICI bytes; quantization error is carried in a local
error-feedback buffer (1-bit-Adam style), so convergence is preserved.

``quantized_psum_mean`` runs INSIDE shard_map (explicit-DP training path;
see examples/train_lm.py --compress-grads).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def quantize_int8(x: Array) -> tuple[Array, Array]:
    """Per-tensor symmetric int8.  Returns (q, scale)."""
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: Array, scale: Array) -> Array:
    return q.astype(jnp.float32) * scale


def quantized_psum_mean(x: Array, axis: str, n_shards: int) -> Array:
    """Mean over ``axis`` with int8 wire format (inside shard_map).

    x f32[N] with N % n_shards == 0 (caller pads).
    """
    n = x.shape[0]
    chunks = x.reshape(n_shards, n // n_shards)
    q, scale = quantize_int8(chunks.reshape(-1))
    q = q.reshape(n_shards, n // n_shards)
    # each shard receives every peer's copy of ITS chunk (int8 wire)
    recv = jax.lax.all_to_all(q[:, None, :], axis, split_axis=0,
                              concat_axis=1, tiled=False)  # [1,S,chunk]
    scales = jax.lax.all_gather(scale, axis)               # [S]
    summed = (recv[0].astype(jnp.float32) *
              scales[:, None]).sum(axis=0) / n_shards      # local chunk mean
    q2, s2 = quantize_int8(summed)
    out = jax.lax.all_gather(q2, axis)                     # [S, chunk] int8
    out_s = jax.lax.all_gather(s2, axis)                   # [S]
    return (out.astype(jnp.float32) * out_s[:, None]).reshape(n)


def make_compressed_grad_fn(loss_fn, mesh: Mesh, axis: str):
    """Explicit-DP gradient step: per-shard grads -> int8 mean -> update.

    Error feedback: the quantization residual of THIS step is added to
    the NEXT step's gradient (carried as an extra state pytree).
    """
    n_shards = int(mesh.shape[axis])

    def grads_with_feedback(params, batch, err):
        loss, g = jax.value_and_grad(loss_fn)(params, batch)

        def one(gl, el):
            flat = gl.reshape(-1) + el.reshape(-1)
            n = flat.shape[0]
            pad = (-n) % n_shards
            flat_p = jnp.pad(flat, (0, pad))
            mean = quantized_psum_mean(flat_p, axis, n_shards)
            new_err = flat_p - mean          # residual kept locally
            return (mean[:n].reshape(gl.shape),
                    new_err[:n].reshape(gl.shape))

        out = jax.tree.map(one, g, err)
        new_g = jax.tree.map(lambda t: t[0], out,
                             is_leaf=lambda t: isinstance(t, tuple))
        new_e = jax.tree.map(lambda t: t[1], out,
                             is_leaf=lambda t: isinstance(t, tuple))
        return loss, new_g, new_e

    def wrapped(params, batch, err):
        fn = jax.shard_map(
            functools.partial(grads_with_feedback),
            mesh=mesh,
            in_specs=(P(), P(axis), P()),
            out_specs=(P(), P(), P()),
            check_vma=False)
        return fn(params, batch, err)

    return wrapped


def zeros_like_error(params: Any) -> Any:
    return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
