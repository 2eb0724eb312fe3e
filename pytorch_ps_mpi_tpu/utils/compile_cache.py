"""One placement rule for JAX's persistent compilation cache.

Every process that compiles — the CLIs, ``chipbench``, ``chip_smoke.py``,
``__graft_entry__.py`` and the fleet's child processes — calls
:func:`enable_compilation_cache` before its first jit. The cache
directory is no program's argument: it is where
``JAX_COMPILATION_CACHE_DIR`` says when that variable is set (jax reads
it itself; nothing is set here, so an outside placement is never
overridden), and otherwise one fixed directory inside the checkout. A
path that moves between runs (``/tmp``, a pid, a timestamp) never hits.
"""

from __future__ import annotations

import os
from typing import Any, Dict

# <repo>/.jax_cache — listed in .gitignore
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


class CompileCacheStats:
    """This process's persistent-cache directory and its hit / miss
    counts since :func:`enable_compilation_cache` (jax counts a miss
    when it writes a newly compiled program to the cache)."""

    def __init__(self, directory: str) -> None:
        self.dir = directory
        self.hits = 0
        self.misses = 0

    def _on_event(self, event: str, **_: Any) -> None:
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1

    def as_dict(self) -> Dict[str, Any]:
        return {"dir": self.dir, "hits": self.hits, "misses": self.misses}


def enable_compilation_cache() -> CompileCacheStats:
    """Turn the persistent cache on; returns the counters for this
    process (callers that report nothing may drop them)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # cache every program, not only those over jax's 1 s default: a cold
    # process otherwise compiles its many sub-second programs again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    stats = CompileCacheStats(jax.config.jax_compilation_cache_dir)
    jax.monitoring.register_event_listener(stats._on_event)
    return stats
