"""JAX's persistent compilation cache for the entry points.

Called from ``launch/train.py``, ``launch/serve.py`` and ``chip_smoke.py``
when they run as programs — never on import and never in tests, which
keep JAX's default of no persistent cache.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is used as it is (JAX reads
    it itself) and no other directory is set.  Otherwise the cache lives
    at ``<repo>/.jax_cache``: a fixed path, so a later run of this
    checkout finds what an earlier one compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
