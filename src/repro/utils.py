"""Small shared utilities used across the kernel and serving stacks."""
from __future__ import annotations

import os
import pathlib

# Root of the checkout (src/repro/utils.py -> two levels up from the
# package): the home of the in-tree compile and autotune caches.
REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


def next_pow2(n: int) -> int:
    """Smallest power of two >= ``n``, with a floor of 1.

    ``next_pow2(0) == next_pow2(1) == 1``: the degenerate sizes that used
    to be handled (identically) by two private copies in
    kernels/autotune.py and runtime/backends.py -- this is the single
    tested definition both now share (tests/test_scheduler.py).
    """
    return 1 << max(0, int(n) - 1).bit_length()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points call this at start-up.  ``JAX_COMPILATION_CACHE_DIR``,
    when set, is the directory (JAX reads it itself) and no other is set;
    otherwise the cache lives at ``<checkout>/.jax_cache``, a fixed path,
    because the path is part of what a later process must match to hit.
    Every compile is kept, however short: a cold process otherwise
    recompiles each small kernel.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
