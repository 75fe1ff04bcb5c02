"""JAX's persistent compilation cache, kept at one fixed place.

A resumed sweep, a repeated benchmark run or a second smoke run re-traces
the same jitted programs and would pay full XLA compilation for each.  The
cache key includes the directory, so it only hits when every run uses the
same one:

- ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (the machine's
  own setting wins; nothing here overrides it);
- otherwise ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

:func:`enable` applies that rule; ``chip_smoke.py``,
``examples/resnet18_bcd_pipeline.py``, ``examples/family_bcd_sweep.py`` and
``benchmarks/bench_bcd_eval.py`` call it before their first jit.
:class:`HitCounter` counts cache hits and misses from JAX's monitoring
events so runners can log how much a restart reused.  Cache keys include
jax/XLA versions and compile options, so a stale directory is never
incorrect — just cold.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

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


class HitCounter:
    """Counts persistent-cache hits/misses via jax's monitoring events.

    jax only exposes the persistent compilation cache's effectiveness as
    telemetry events; this adapter turns them into a queryable counter so
    runners can print "N of M compiles served from cache" at exit.
    """

    def __init__(self) -> None:
        """Subscribe to the cache-hit/miss monitoring events."""
        import jax

        self.hits = 0
        self.misses = 0

        def _on_event(event: str, **kw) -> None:
            if event == _HIT_EVENT:
                self.hits += 1
            elif event == _MISS_EVENT:
                self.misses += 1

        jax.monitoring.register_event_listener(_on_event)

    def summary(self) -> Dict[str, int]:
        """``{"hits": N, "misses": M}`` observed since construction."""
        return {"hits": self.hits, "misses": self.misses}

    def log_line(self) -> str:
        """One human-readable line for the runner's exit log."""
        total = self.hits + self.misses
        return (f"[compile-cache] {self.hits}/{total} compile requests "
                f"served from the persistent cache")


def hit_counter() -> HitCounter:
    """Construct a :class:`HitCounter` (call before the jits you care
    about; events fired earlier are not replayed)."""
    return HitCounter()


def entry_count(cache_dir: Optional[str]) -> int:
    """Number of cached executables under ``cache_dir`` (0 if unset or
    missing) — a coarse cross-process complement to :class:`HitCounter`.
    """
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(1 for name in os.listdir(cache_dir)
               if not name.startswith("."))
