"""Where JAX's persistent compilation cache lives for the entry points
(``chip_smoke.py``, ``python -m repro.launch.serve``, the
``benchmarks/bench_round.py`` workers).

Call ``use_compile_cache()`` at the top of an entry point's ``main`` —
never at import, so importing a module never changes JAX's configuration.
"""
from __future__ import annotations

import os

# the checkout root: src/repro/launch/compile_cache.py -> three levels up
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads
    it itself: nothing is set here. Otherwise the cache is the fixed
    ``<checkout>/.jax_cache`` — never a temp name, pid or time, so the
    next run from the same checkout finds what this one wrote.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
