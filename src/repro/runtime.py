"""Process-wide JAX settings shared by the kernels and the entry points.

Nothing here runs at import: the kernels ask :func:`on_tpu` when they
trace, and each entry point's ``main()`` calls
:func:`enable_compile_cache` once.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/src/repro/runtime.py -> <checkout>
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU.

    A backend that fails to initialise raises here instead of reading as
    "not a TPU": the kernels would otherwise fall back to interpret mode
    and hide a broken chip behind a slow, correct-looking run.
    """
    return jax.default_backend() == "tpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as it is (JAX
    reads it itself).  Otherwise the cache lives at a fixed
    ``<checkout>/.jax_cache``: the path is part of the cache key, so a
    directory that moves between runs never hits.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
