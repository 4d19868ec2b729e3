"""Where the persistent XLA compilation cache lives.

One rule, applied by every entry point (train/main_trainer.py,
train/main_bert.py, bench.py's children, chip_smoke.py) before its first
compile: if ``JAX_COMPILATION_CACHE_DIR`` is set the cache is placed from
outside and this code sets nothing (jax reads the variable itself);
otherwise it is ``<checkout>/.jax_cache`` — a fixed path derived from the
package's own location, because the directory is part of the cache key and
a path that moves (a temp name, a pid, a time) never hits.
"""

from __future__ import annotations

import os


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``: the directory that holds ``oktopk_tpu/``."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def ensure_compile_cache() -> str:
    """Place the compilation cache and return the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
