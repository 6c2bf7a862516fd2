"""Elastic scaling: re-shard state onto whatever mesh a restart sees.

A 1000-node job loses hosts; the restart builds the largest healthy mesh
and resumes.  Because checkpoints are logical pytrees (host numpy) and
partition specs are FUNCTIONS of (tree, mesh) — not baked into the
checkpoint — restoring onto a different device count is just
``device_put`` with the new mesh's NamedShardings.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.train import checkpoint as ckpt_lib


def largest_mesh(axis_names: tuple[str, ...] = ("data", "model"),
                 model_parallelism: int = 1) -> Mesh:
    """Build the biggest mesh the surviving devices allow.

    ``model_parallelism`` is pinned (weights must fit); the data axis
    absorbs whatever device count remains — elastic data parallelism.
    """
    n = len(jax.devices())
    model = min(model_parallelism, n)
    data = n // model
    return jax.make_mesh((data, model), axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def shardings_for(tree: Any, mesh: Mesh,
                  spec_fn: Callable[[tuple, Any], P]) -> Any:
    """Pytree of NamedSharding from a (path, leaf) -> PartitionSpec rule."""
    def one(path, leaf):
        return NamedSharding(mesh, spec_fn(path, leaf))
    return jax.tree_util.tree_map_with_path(one, tree)


def reshard(tree: Any, shardings: Any) -> Any:
    return jax.tree.map(jax.device_put, tree, shardings)


def recover(ckpt_dir: str, template: Any, mesh: Mesh,
            spec_fn: Callable[[tuple, Any], P]) -> tuple[Any, int]:
    """Restore the latest checkpoint directly onto ``mesh``.

    Returns (state_tree, step).  Works for ANY device count: this is the
    elastic-restart entry point.
    """
    sh = shardings_for(template, mesh, spec_fn)
    return ckpt_lib.restore(ckpt_dir, template, shardings=sh)
