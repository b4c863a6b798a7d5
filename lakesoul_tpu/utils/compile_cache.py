"""Where this checkout keeps JAX's persistent compilation cache.

Called by each entry point that owns a process (``chip_smoke.py``,
``benchmarks/chip/run.py``, the examples, ``fleet train``, ``__graft_entry__``),
never at package import: a library must not redirect its host's cache.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed place and return it.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set — JAX reads it itself, so
    nothing is set in code and a cache placed from outside is found again.
    Otherwise the cache lives at ``<checkout>/.jax_cache``: the path is part
    of what keeps entries reusable, so it is never a temporary name, a pid
    or a timestamp."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
