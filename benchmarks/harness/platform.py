"""Start jax for a benchmark process: the compile cache, then the devices."""
from __future__ import annotations

import sys
import time


def start_jax(rehearse: bool, chips: int, who: str):
    """(cache dir, devices, {"platform", "kind", "count"}, seconds the
    accelerator's runtime took to start), or None when this is no rehearsal
    and jax has no TPU or too few chips — the caller then exits 1 with no
    result."""
    # importing the package configures jax (x64); the cache helper must run
    # before the first compile.  JAX_COMPILATION_CACHE_DIR wins when set,
    # else <checkout>/.jax_cache — a fixed path, so the second run hits.
    from siddhi_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    # every program of the cell, the small ones too, is found again by the
    # next run: set-up after a cell's first run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    t = time.perf_counter()
    devs = jax.devices()
    runtime_start_s = time.perf_counter() - t
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not rehearse:
        if device["platform"] != "tpu":
            print(f"{who}: jax found no TPU (devices: {device}); nothing is "
                  f"measured on another platform — `--rehearse` walks the "
                  f"code at tiny sizes", file=sys.stderr)
            return None
        if len(devs) < chips:
            print(f"{who}: the cell needs {chips} chip(s), jax has "
                  f"{len(devs)}", file=sys.stderr)
            return None
    return cache_dir, devs, device, runtime_start_s
