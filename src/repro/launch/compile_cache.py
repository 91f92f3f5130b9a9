"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, ``repro.launch.train``, ``python -m
repro.campaign run|report``, ``python -m repro.serve``) call
:func:`enable_compile_cache` before their first compile; importing this
module changes nothing.
"""
from __future__ import annotations

import os
import sys

#: the cache when the environment names none: ``<repo>/.jax_cache``,
#: ignored by git.  A fixed path, so every process of this checkout finds
#: what the others compiled.
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and nothing is set here.  Otherwise the variable is set to
    :data:`DEFAULT_DIR`, for this process and the processes it starts;
    JAX reads it when imported, so a process that has imported JAX
    already is also told through ``jax.config``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    os.environ["JAX_COMPILATION_CACHE_DIR"] = DEFAULT_DIR
    if "jax" in sys.modules:
        import jax
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
