"""Backend selection shared by every Pallas kernel in this package.

Kernels take ``interpret: bool | None = None``; ``None`` resolves to
"interpret unless we are actually on a TPU", so the same call sites run
the Python interpreter on CPU (semantics validated everywhere) and the
compiled Mosaic kernel on real hardware — no hardcoded ``interpret=True``
defaults to flip before a TPU run.
"""
from __future__ import annotations

import os
import pathlib

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """Explicit flag wins; otherwise compile only on a real TPU backend."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed place; call
    from an entry point's ``main()``, never at import.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself and
    nothing else is configured); otherwise the cache lives at
    ``<checkout>/.jax_cache`` — a path with no temp name, pid or time in
    it, since the path is part of what makes a later run hit.  Returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
