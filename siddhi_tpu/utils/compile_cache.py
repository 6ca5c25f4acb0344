"""Where the persistent XLA compile cache lives — decided outside the code.

Every entry script (`chip_smoke.py`, `bench.py`) calls
`enable_compile_cache()` once before its first compile.  The rule:

- `JAX_COMPILATION_CACHE_DIR` set: jax already reads it, so this module
  sets NO directory — the cache belongs to whoever placed it.
- unset: `<checkout>/.jax_cache` (listed in `.gitignore`).  The path is
  fixed — never a temp name, pid or time — because a directory that moves
  never hits.

**Op names are part of a cache entry's key** (`jax_compilation_cache_
include_metadata_in_key`, set here in both cases; jax's default leaves them
out).  The pattern programs' `jax.named_scope` sections reach a profiler
trace as each op's name (core/pattern_planner.py), and an executable keeps
the names it was COMPILED with: with the names out of the key, a cache
filled by a tree whose scopes differ hands this tree back its own programs
under the other tree's names, and the trace's sections are then the other
tree's.  The chip machine shares one placed cache between a parent and a
change.  The price: an edit that moves a traced line compiles that program
once more, in the first run after it.

Besides that only the directory is set.
`jax_persistent_cache_min_compile_time_secs`
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
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
