"""JAX's persistent compilation cache, kept at one fixed place.

A resumed sweep, a repeated benchmark run or a second smoke run re-traces
the same jitted programs and would pay full XLA compilation for each.  The
cache key includes the directory, so it only hits when every run uses the
same one:

- ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (the machine's
  own setting wins; nothing here overrides it);
- otherwise ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

:func:`enable` applies that rule; ``chip_smoke.py``,
``examples/resnet18_bcd_pipeline.py`` and ``examples/family_bcd_sweep.py``
call it before their first jit.  The
runners count cache hits and misses with ``core.tracing.recording()`` and
print :func:`log_line` of its counts, so a restart shows how much it
reused.  Cache keys include jax/XLA versions and compile options, so a
stale directory is never incorrect — just cold.
"""
from __future__ import annotations

import os
from typing import Mapping, Optional

#: The fixed in-checkout cache directory (used when the environment names
#: none).
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def cache_dir() -> str:
    """The cache directory: ``JAX_COMPILATION_CACHE_DIR`` if set, else
    :data:`REPO_CACHE_DIR`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR


def enable() -> str:
    """Turn on JAX's persistent compilation cache at :func:`cache_dir`;
    returns the directory.

    Safe to call before or after the first jit; creates the directory.
    Thresholds are zeroed so every executable is cached — sweeps and smoke
    runs re-jit many small programs, exactly the population the default
    "only big/slow compiles" policy would skip.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    d = cache_dir()
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # jax latches "is the cache used?" on the first compile of the process;
    # unlatch it so the directory takes effect even after an earlier jit
    compilation_cache.reset_cache()
    return d


def log_line(counts: Mapping[str, int]) -> str:
    """One line for a runner's exit log from a ``core.tracing`` recorder's
    ``counts``: how many compile requests the persistent cache served."""
    hits = counts.get("jax.cache_hit", 0)
    total = hits + counts.get("jax.cache_miss", 0)
    return (f"[compile-cache] {hits}/{total} compile requests served from "
            "the persistent cache")


def entry_count(cache_dir: Optional[str]) -> int:
    """Number of cached executables under ``cache_dir`` (0 if unset or
    missing) — a coarse cross-process complement to :func:`log_line`.
    """
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(1 for name in os.listdir(cache_dir)
               if not name.startswith("."))
