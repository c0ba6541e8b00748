"""Where compiled programs are kept between runs of one checkout."""
from __future__ import annotations

import os

__all__ = ["CHECKOUT", "use_compile_cache"]

# the directory that holds the paddle_tpu package: what this repo reads
# and writes besides its arguments stays under it
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. Where JAX_COMPILATION_CACHE_DIR is set JAX
    already keeps its cache there and nothing is set here; otherwise the
    cache is `<checkout>/.jax_cache` — a fixed path, because the path is
    part of the cache key and a directory that moves never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
