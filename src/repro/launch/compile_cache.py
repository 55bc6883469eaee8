"""Where JAX's persistent compilation cache lives for this repo's scripts.

Entry points (`chip_smoke.py`, `benchmarks/run.py`, the examples) call
`use_compile_cache()` once, before their first compile.  Library modules
never set a cache.

* `JAX_COMPILATION_CACHE_DIR` set: nothing to do — JAX reads the
  variable itself, so the cache goes there and nowhere else.
* unset: the cache goes to `<checkout>/.jax_cache`, one fixed path
  (git-ignored).  The path is part of the cache key, so it is never
  made from a temp name, a pid or the time: a directory that moves
  never hits.
"""

from __future__ import annotations

import os

#: the checkout root: src/repro/launch/compile_cache.py -> three levels up
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its one directory and
    return that directory (see the module docstring for which one)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
