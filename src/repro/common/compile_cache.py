"""Where the launch CLIs keep JAX's persistent compilation cache.

The directory must be a fixed path: entries written under a temporary or
per-run name are never found again by the next run.
"""

import os

import jax

# <repo>/.jax_cache (this file is <repo>/src/repro/common/compile_cache.py)
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def place_compile_cache():
    """Point JAX's persistent compilation cache at its directory and
    return that directory. When `JAX_COMPILATION_CACHE_DIR` is set, JAX
    already reads it and nothing is changed; otherwise the cache goes to
    `<repo>/.jax_cache`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
