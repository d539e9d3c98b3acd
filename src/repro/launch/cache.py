"""JAX's persistent compilation cache for the entry points.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.build_graph``, ``benchmarks.run``) calls
``enable_compile_cache()`` first thing in its ``main``; nothing calls it at
import.  The cache lives where ``JAX_COMPILATION_CACHE_DIR`` says when that is
set (JAX reads the variable itself), and otherwise at the fixed in-checkout
path ``<repo>/.jax_cache`` (git-ignored).  The directory is part of the
cache's key, so it is never derived from a temporary name, a process id or
the time: a path that moves never hits.
"""

from __future__ import annotations

import os

import jax

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(_REPO, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
