"""Placement of JAX's persistent compilation cache for the entry points.

``chip_smoke.py`` and the ``main()`` of ``launch.train``, ``launch.serve``
and ``launch.zoo`` call :func:`enable_compile_cache` once, before they
compile anything.  Nothing calls it at import: tests import those
modules, and a compile for a described (not attached) TPU must not be
written to a cache that a later run on the chip would read.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# a fixed path inside the checkout (listed in .gitignore): never made
# from a temporary name, a pid or the time, so a later run finds it again
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and this sets no other directory.  Otherwise the cache
    goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
