"""Where the persistent XLA compile cache lives — decided outside the code.

Every entry script (`chip_smoke.py`, `bench.py`) calls
`enable_compile_cache()` once before its first compile.  The rule:

- `JAX_COMPILATION_CACHE_DIR` set: jax already reads it, so this module
  touches NOTHING in jax's config — the cache belongs to whoever placed it.
- unset: `<checkout>/.jax_cache` (listed in `.gitignore`).  The path is
  fixed — never a temp name, pid or time — because a directory that moves
  never hits.

Only the directory is set.  `jax_persistent_cache_min_compile_time_secs`
stays at jax's default of 1 s, deliberately: on the v5e a cold
`chip_smoke.py` compiles 122 programs in 315 s, of which the 12 that take
>= 1 s are 305 s and the 110 under 1 s are 9.6 s together (my chip run,
PR 21 — PERF.md).  A threshold of 0 would save those 9.6 s of a cold call
for 110 more entries; the 5 s this repo used before left 7.6 s more
uncached.  Neither moves a cold start that eight programs own.
"""
from __future__ import annotations

import os


def enable_compile_cache() -> str:
    """Point jax at the persistent compile cache (see module docstring)
    and return the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
