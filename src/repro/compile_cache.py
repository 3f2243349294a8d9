"""Where JAX's persistent compilation cache lives for this repository's
entry points (``chip_smoke.py``, ``python -m benchmarks.run``).

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed
path, so a later process on the same checkout finds the programs an earlier
one compiled.  Never a temporary name, a pid or the time — a directory that
moves never hits.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; call before the first
    lowering.  Returns the directory the cache uses."""
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
