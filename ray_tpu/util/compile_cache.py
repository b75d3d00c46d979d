"""Where XLA's persistent compile cache lives.

Every process that compiles for the chip calls `use_compile_cache()`
before its first compile: the train worker, the LLM engine replica,
`chip_smoke.py`'s tasks. The operator places the cache with
`JAX_COMPILATION_CACHE_DIR`, which JAX reads itself; without it the
cache is `<checkout>/.jax_cache`, a fixed path worked out from where the
package lies, because the path is part of every entry's key and a
directory that moves never hits. Without the variable a process pinned
to the CPU caches nothing.
"""
from __future__ import annotations

import os


def compile_cache_dir() -> str:
    """The cache's path; touches neither JAX nor the disk."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def use_compile_cache() -> str:
    """Point this process's JAX at the compile cache; returns its path."""
    path = compile_cache_dir()
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return path         # JAX has read the variable itself
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # a process pinned to the CPU (a worker without a chip grant,
        # the tests): its compiles are cheap, and XLA:CPU logs an error
        # about machine features for every entry it loads
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    return path
