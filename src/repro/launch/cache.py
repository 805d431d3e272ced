"""JAX's persistent compilation cache, placed from outside.

Called from the ``main()`` of the chip smoke test, ``launch/serve.py`` and
``benchmarks/bench_serving.py`` — never at import.  Compiles stay set-up
time: the cache only makes a repeated run's set-up shorter.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed directory inside the checkout (listed in .gitignore), so a later
# run from the same checkout finds what an earlier one wrote
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at a directory; return it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
    stands; otherwise the cache goes to :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
