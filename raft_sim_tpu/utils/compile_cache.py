"""Where JAX's persistent compilation cache lives for this repo's entry points.

Called at the top of `driver.main`, `bench.main` and `chip_smoke.py`, before the
first compile -- never on package import, so a library user's own cache setting
is left alone. A set `JAX_COMPILATION_CACHE_DIR` wins (JAX reads it itself);
otherwise the cache goes to `<repo>/.jax_cache` (git-ignored). The path is fixed,
never derived from a temp name, pid or time: it is part of the cache key, so a
directory that moves never hits.
"""

from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir
